"""Roofline share (%) of paged_flash_decode on a model whose layers are
windowed or full: per kernel call, max(bytes / HBM peak, FLOPs / peak)
of one layer's live keys (min(length, window) on a windowed layer),
averaged over the pattern's layers and the traced rounds, over the
kernel's device time per call."""
import numpy as np

from bench import flops_moe, trace_reduce
from bench.readers import NAMES, traced_dispatches


def read(run):
    if run.trace is None:
        return None
    calls = [d["decode"] for d in traced_dispatches(run) if d["decode"]]
    got = trace_reduce.per_call_s(run.trace, "ops", NAMES["decode_kernel"])
    if not calls or got is None:
        return None
    c, pk = run.cell.config, run.peaks
    need = [max(f / pk["bf16_flops_per_s"], b / pk["hbm_bytes_per_s"])
            for lengths in calls
            for f, b in flops_moe.attention_layers(c, lengths)]
    return 100.0 * float(np.mean(need)) / got[0]
