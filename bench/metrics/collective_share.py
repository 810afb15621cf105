"""Share (%) of the paged decode program's device time spent in
collective operations, per chip, averaged over the chips."""
from bench import trace_reduce
from bench.readers import NAMES


def read(run):
    if run.trace is None:
        return None
    shares = []
    for chip in run.trace.chips:
        runs = trace_reduce.inside(
            trace_reduce.matching(chip.modules, NAMES["decode_step"]),
            run.trace.window)
        total = sum(r.end - r.start for r in runs)
        if not total:
            continue
        coll = trace_reduce.within(
            trace_reduce.matching(chip.ops, NAMES["collective"]), runs)
        shares.append(sum(o.end - o.start for o in coll) / total)
    return 100.0 * sum(shares) / len(shares) if shares else None
