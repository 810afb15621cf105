"""Roofline share (%) of the paged decode step of a sparse-expert model:
per traced round, max(bytes / HBM peak, FLOPs / peak) from
``bench/flops_moe.py`` for its live lanes, over the device time per run
of ``jit_paged_decode_step``.  The experts are counted as uniform
routing reaches them, an upper bound where routing is skewed (see
``bench/flops_moe.py``)."""
import numpy as np

from bench import flops_moe, trace_reduce
from bench.readers import NAMES, traced_dispatches


def read(run):
    if run.trace is None:
        return None
    calls = [d["decode"] for d in traced_dispatches(run) if d["decode"]]
    got = trace_reduce.per_call_s(run.trace, "modules", NAMES["decode_step"])
    if not calls or got is None:
        return None
    c, pk = run.cell.config, run.peaks
    need = []
    for lengths in calls:
        f, b = flops_moe.decode_round(c, lengths)
        need.append(max(f / pk["bf16_flops_per_s"], b / pk["hbm_bytes_per_s"]))
    return 100.0 * float(np.mean(need)) / got[0]
