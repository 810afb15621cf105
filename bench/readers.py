"""Arithmetic shared by the metric readers in ``bench/metrics/``.

Each reader is ``read(run) -> float | None`` over a
``harness.RunRecord``; None means the run holds nothing to read (the
harness then leaves the metric out of the result line).
"""
from __future__ import annotations

import json
import pathlib
from typing import Iterable, Optional

import numpy as np

from bench import flops, trace_reduce

NAMES = json.loads((pathlib.Path(__file__).resolve().parent
                    / "trace_names.json").read_text())


def p95(values: Iterable[float]) -> Optional[float]:
    """95th percentile (linear interpolation) of all values, or None."""
    v = np.asarray(list(values), np.float64)
    return float(np.percentile(v, 95)) if v.size else None


def finished(run) -> list:
    return [r for r in run.in_window() if r.status == "ok"]


# ---------------------------------------------------------------------------
# traced slice
# ---------------------------------------------------------------------------


def traced_dispatches(run) -> list:
    """The engine dispatches recorded inside the traced slice."""
    if not run.trace_span:
        return []
    lo, hi = run.trace_span
    return [d for d in run.dispatches if lo <= d["t"] < hi]


def module_ms(run, key: str) -> Optional[float]:
    """Device milliseconds per run of the program named ``key``."""
    if run.trace is None:
        return None
    got = trace_reduce.per_call_s(run.trace, "modules", NAMES[key])
    return None if got is None else got[0] * 1e3


def model_shards(run) -> int:
    """Chips that share one layer's work ('model' axis of the mesh)."""
    mesh = run.cell.config["serving"].get("mesh")
    return int(mesh[-1]) if mesh else 1


def decode_roofline(run) -> Optional[float]:
    """Share (%) of the paged decode kernel's roofline: per kernel call,
    max(bytes / HBM peak, FLOPs / peak) for the live context it needs,
    over the kernel's measured device time per call (one chip's share
    of the heads where the kernel is sharded)."""
    if run.trace is None:
        return None
    calls = [d["decode"] for d in traced_dispatches(run) if d["decode"]]
    got = trace_reduce.per_call_s(run.trace, "ops", NAMES["decode_kernel"])
    if not calls or got is None:
        return None
    c, pk, m = run.cell.config, run.peaks, model_shards(run)
    need = []
    for lengths in calls:
        f, b = flops.decode_attention(c, lengths)
        need.append(max(f / pk["bf16_flops_per_s"],
                        b / pk["hbm_bytes_per_s"]) / m)
    return 100.0 * float(np.mean(need)) / got[0]


def step_mfu(run) -> Optional[float]:
    """Model FLOPs of every token prefilled or decoded in the traced
    slice, over the slice's length times the chips' peak (%)."""
    if not run.trace_span:
        return None
    c = run.cell.config
    total = 0
    for d in traced_dispatches(run):
        total += sum(flops.prefill_flops(c, s, n) for s, n in d["prefill"])
        total += sum(flops.token_flops(c, n) for n in d["decode"])
    span = run.trace_span[1] - run.trace_span[0]
    return 100.0 * total / (span * run.chips
                            * run.peaks["bf16_flops_per_s"])


def idle_share(run) -> Optional[float]:
    if run.trace is None:
        return None
    return 100.0 * (1.0 - trace_reduce.busy_s(run.trace)
                    / run.trace.window_s)
