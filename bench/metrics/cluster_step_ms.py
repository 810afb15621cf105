"""Host time per EdgeCluster.step (dispatch on every engine, then
collect on every engine) in the traced slice, ms."""


def read(run):
    if not run.trace_span:
        return None
    lo, hi = run.trace_span
    n = sum(lo <= t < hi for t, _ in run.steps)
    return (hi - lo) / n * 1e3 if n else None
