"""95th percentile of the host time of EdgeCluster.submit in the
window (observe + scheduler select + admit), ms."""
from bench.readers import p95


def read(run):
    return p95(d * 1e3 for _, d in run.submits)
