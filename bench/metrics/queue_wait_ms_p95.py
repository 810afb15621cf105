"""95th percentile of the engine queue wait, admission to first prefill
chunk (t_prefill_start - t_enqueue), over requests due in the window
whose prefill began before it closed, ms."""
from bench.readers import finished, p95


def read(run):
    return p95((r.t_prefill_start - r.t_enqueue) * 1e3
               for r in finished(run) if r.t_prefill_start < run.t_close)
