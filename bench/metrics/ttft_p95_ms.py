"""95th percentile, over every request due in the window, of the time
from its due time to its first token (ms)."""
from bench.readers import finished, p95


def read(run):
    return p95((r.t_prefill_end - r.t_arrival) * 1e3 for r in finished(run))
