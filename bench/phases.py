"""Where the host's time goes, read from the program's own spans.

    python3 bench/phases.py --workload <cell> --seeds 1,2,3 --seconds 51 \\
        --trace 1,0,0 --record 1,1,0

Runs the cell once per seed in ONE process (the set-up compiles once),
as ``bench/run.py`` does, each run with the profiler's slice on or off
(``--trace``) and the program's span recorder (``repro.tracing``) on or
off (``--record``), one value per seed or one for all.  The recorder
runs from the lead-in to the end of the window.  Prints one JSON line
per seed: the run's own metrics, and from the spans that start in the
window and lie outside the profiled slice (so that the profiler's own
host cost stays out):

* ``select_ms_p95``: p95 of ``repro.scheduler.select``, one per arrival,
  and ``select_sync_share``, the share of it in ``repro.scheduler.sync``;
* ``dispatch_host_ms``: mean ``repro.engine.dispatch``;
* ``lane_occupancy``: active decode lanes over ``max_lanes`` per
  ``repro.engine.decode`` (%);
* ``admission``: requests admitted, copy-on-write forks, and admission
  ms per request admitted;
* ``slowest``: the longest submits, dispatches and prefill chunks, each
  with its attributes (``rid``, ``engine``, ``lane``, ``start``,
  ``tokens``);
* ``phases``: count, mean and mean self milliseconds of every span name.

With ``--trace 1`` also ``phases_in_slice`` and ``dispatch_host_ms_in_slice``
(the same read inside the profiled slice), and ``idle_gaps`` and
``idle_by_span``: the slice's device idle gaps named by the innermost
``bench.*`` or ``repro.*`` span on the trace's clock.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import sys
import time
from typing import Dict, List, Optional, Sequence

import numpy as np

ROOT = pathlib.Path(__file__).resolve().parents[1]
PREFIX = "repro."
# spans whose longest instances the summary lists, with their attributes
SLOWEST = ("repro.cluster.submit", "repro.engine.dispatch",
           "repro.engine.prefill")


# ---------------------------------------------------------------------------
# reading spans (each a repro.tracing.Span: name, start, end, parent, attrs)
# ---------------------------------------------------------------------------


def in_window(spans: Sequence, t_open: float, t_close: float,
              slice_: Sequence[float] = (), inside: bool = False
              ) -> List[int]:
    """Indices of the closed spans that start in the window and lie
    wholly outside the profiled slice ``(lo, hi)`` (or, ``inside``,
    start in it)."""
    lo, hi = slice_ if slice_ else (float("inf"), float("inf"))
    out = []
    for i, s in enumerate(spans):
        if s.end is None or not t_open <= s.start < t_close:
            continue
        if (lo <= s.start < hi) if inside else (s.end <= lo or s.start >= hi):
            out.append(i)
    return out


def _ms(s) -> float:
    return (s.end - s.start) * 1e3


def select_ms_p95(spans: Sequence, keep: Sequence[int]) -> Optional[float]:
    d = [_ms(spans[i]) for i in keep
         if spans[i].name == "repro.scheduler.select"]
    return float(np.percentile(d, 95)) if d else None


def select_sync_share(spans: Sequence, keep: Sequence[int]
                      ) -> Optional[float]:
    """Share (%) of the select spans' time spent in their sync child."""
    sel = {i for i in keep if spans[i].name == "repro.scheduler.select"}
    total = sum(_ms(spans[i]) for i in sel)
    sync = sum(_ms(s) for s in spans if s.name == "repro.scheduler.sync"
               and s.end is not None and s.parent in sel)
    return 100.0 * sync / total if total else None


def dispatch_host_ms(spans: Sequence, keep: Sequence[int]
                     ) -> Optional[float]:
    d = [_ms(spans[i]) for i in keep
         if spans[i].name == "repro.engine.dispatch"]
    return float(np.mean(d)) if d else None


def lane_occupancy(spans: Sequence, keep: Sequence[int], max_lanes: int
                   ) -> Optional[float]:
    lanes = [spans[i].attrs["lanes"] for i in keep
             if spans[i].name == "repro.engine.decode"]
    return 100.0 * sum(lanes) / (len(lanes) * max_lanes) if lanes else None


def admission(spans: Sequence, keep: Sequence[int]) -> dict:
    """Requests admitted and pages forked copy-on-write, and admission
    host ms per request admitted."""
    ad = [spans[i] for i in keep if spans[i].name == "repro.engine.admission"]
    n = sum(s.attrs["admitted"] for s in ad)
    return {"admitted": n, "forks": sum(s.attrs["forks"] for s in ad),
            "ms_per_admitted": sum(map(_ms, ad)) / n if n else None}


def slowest(spans: Sequence, keep: Sequence[int], name: str, k: int = 5
            ) -> List[list]:
    """The ``k`` longest spans called ``name``: ms and attributes."""
    got = sorted((spans[i] for i in keep if spans[i].name == name),
                 key=_ms, reverse=True)[:k]
    return [[_ms(s), s.attrs] for s in got]


def phases(spans: Sequence, keep: Sequence[int]) -> Dict[str, dict]:
    """Per span name: count, mean ms, and mean self ms (the span less
    its children)."""
    child: Dict[int, float] = {}
    for s in spans:
        if s.parent is not None and s.end is not None:
            child[s.parent] = child.get(s.parent, 0.0) + _ms(s)
    out: Dict[str, list] = {}
    for i in keep:
        s = spans[i]
        out.setdefault(s.name, []).append((_ms(s), _ms(s) - child.get(i, 0)))
    return {name: {"count": len(v),
                   "mean_ms": float(np.mean([a for a, _ in v])),
                   "self_ms": float(np.mean([b for _, b in v]))}
            for name, v in sorted(out.items())}


def dispatch_work(spans: Sequence) -> List[dict]:
    """Per engine dispatch, in order: its prefill chunks ``(start,
    tokens)`` and its decode lanes' context lengths, from the attributes
    of its ``repro.engine.prefill`` and ``repro.engine.decode`` spans
    (what ``harness._Tracer`` rebuilds from the engine's internals)."""
    out, at = [], {}
    for i, s in enumerate(spans):
        if s.name == "repro.engine.dispatch":
            at[i] = len(out)
            out.append({"t": s.start, "engine": s.attrs.get("engine"),
                        "prefill": [], "decode": []})
        elif s.parent in at:
            d = out[at[s.parent]]
            if s.name == "repro.engine.prefill":
                d["prefill"].append((s.attrs["start"], s.attrs["tokens"]))
            elif s.name == "repro.engine.decode":
                d["decode"] = list(s.attrs["lengths"])
    return out


# ---------------------------------------------------------------------------
# the profiled slice: idle gaps named by the program's spans
# ---------------------------------------------------------------------------


def program_events(xplane: str) -> list:
    """The host events named ``repro.*`` in a profiler trace."""
    from jax.profiler import ProfileData

    from bench import trace_reduce

    out = []
    for plane in ProfileData.from_file(xplane).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                out += [trace_reduce._event(e) for e in line.events
                        if e.name.startswith(PREFIX)]
    return out


def named_gaps(trace, events: list, k: int = 10) -> dict:
    """The slice's device idle gaps, named by the innermost ``bench.*``
    or ``repro.*`` span: the ``k`` longest, and the idle seconds under
    each span name over all of them."""
    from bench import trace_reduce

    t = trace_reduce.from_parts(trace.chips, trace.spans + events)
    every = trace_reduce.idle_gaps(t, k=1 << 30)
    by: Dict[str, float] = {}
    for name, s in every:
        key = name.split(" at +")[0]
        by[key] = by.get(key, 0.0) + s
    return {"idle_gaps": every[:k],
            "idle_by_span": dict(sorted(by.items(), key=lambda kv: -kv[1]))}


# ---------------------------------------------------------------------------
# one run
# ---------------------------------------------------------------------------


def run_one(root: pathlib.Path, workload: str, seed: int, seconds: float,
            trace: bool, record: bool):
    """``harness.run`` with the recorder on (or off) around its loop;
    returns the result, the run's record and the recorded spans."""
    from bench import harness
    from repro import tracing

    got = {"spans": []}
    real = harness.drive

    def drive(cluster, engines, plan, rec, tracer, compiles):
        got["record"] = rec
        if record:
            tracing.start()
        try:
            real(cluster, engines, plan, rec, tracer, compiles)
        finally:
            got["spans"] = tracing.stop()

    harness.drive = drive
    try:
        res = harness.run(root, workload, seed, seconds, trace,
                          time.perf_counter())
    finally:
        harness.drive = real
    return res, got["record"], got["spans"]


def summary(res: dict, rec, spans: list, root: pathlib.Path) -> dict:
    from bench import trace_reduce

    keep = in_window(spans, rec.t_open, rec.t_close, rec.trace_span)
    lanes = rec.cell.config["serving"]["max_lanes"]
    out = {"correct": res["correct"], "failed": res["failed"],
           "metrics": {k: v["value"] for k, v in res["metrics"].items()},
           "spans": len(spans),
           "select_ms_p95": select_ms_p95(spans, keep),
           "select_sync_share": select_sync_share(spans, keep),
           "dispatch_host_ms": dispatch_host_ms(spans, keep),
           "lane_occupancy": lane_occupancy(spans, keep, lanes),
           "admission": admission(spans, keep),
           "slowest": {n: slowest(spans, keep, n) for n in SLOWEST},
           "phases": phases(spans, keep)}
    if rec.trace is not None:
        inside = in_window(spans, rec.t_open, rec.t_close, rec.trace_span,
                           inside=True)
        out["dispatch_host_ms_in_slice"] = dispatch_host_ms(spans, inside)
        out["phases_in_slice"] = phases(spans, inside)
        out["device"] = res["device"]
        out["device_ops"] = res["breakdown"]["device_ops"]
        xplane = trace_reduce.find_xplane(
            str(root / "bench_out" / "trace" / rec.cell.name))
        out.update(named_gaps(rec.trace, program_events(xplane)))
    return out


def _flags(text: str, n: int) -> List[bool]:
    v = [bool(int(x)) for x in text.split(",")]
    return v * n if len(v) == 1 else v


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", default="0")
    ap.add_argument("--record", default="1")
    args = ap.parse_args(argv)
    for p in (ROOT / "src", ROOT):
        if str(p) not in sys.path:
            sys.path.insert(0, str(p))
    seeds = [int(s) for s in args.seeds.split(",")]
    traces, records = (_flags(args.trace, len(seeds)),
                       _flags(args.record, len(seeds)))
    if not len(seeds) == len(traces) == len(records):
        ap.error("--trace and --record take one value, or one per seed")
    for seed, trace, record in zip(seeds, traces, records):
        res, rec, spans = run_one(ROOT, args.workload, seed, args.seconds,
                                  trace, record)
        print(json.dumps(dict(seed=seed, trace=trace, record=record,
                              **summary(res, rec, spans, ROOT))), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
