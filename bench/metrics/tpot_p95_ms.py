"""95th percentile, over requests, of the time per output token after
the first: (finish - first token) / (tokens - 1), in ms."""
from bench.readers import finished, p95


def read(run):
    return p95((r.t_finish - r.t_prefill_end) / (len(r.tokens) - 1) * 1e3
               for r in finished(run) if len(r.tokens) > 1)
