"""95th percentile of the service delay, due time to last token (ms):
the paper's service delay."""
from bench.readers import finished, p95


def read(run):
    return p95((r.t_finish - r.t_arrival) * 1e3 for r in finished(run))
