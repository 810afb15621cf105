"""Saturation stress benchmark: ramp Poisson arrival rate until goodput
collapses, per scheduler.

The closed-loop benchmark replays ONE modest trace; this harness answers
the capacity question the paper's delay claims hang on: how much offered
load can the fleet absorb before deadline goodput collapses, and which
scheduler holds the knee longest?  (The ramped-load protocol mirrors how
EAT, arXiv:2507.10026, and Two-Timescale Model Caching, arXiv:2411.01458,
evaluate edge schedulers.)

Protocol
--------
For each scheduler, the SAME geometric ladder of offered arrival rates is
replayed stage by stage (identical per-stage traces across schedulers —
same seeds), each stage on a freshly reset fleet.  Each stage offers load for a FIXED window
(``window_s``), so the number of arrivals scales with the stage's rate:
past fleet capacity the backlog — and with it queueing delay and
deadline misses — grows with offered load, which is what makes goodput
collapse instead of merely flattening.  The stress QoS mix carries
deadlines tightened to the benchmark's time scale (the serving defaults
of 2 s / 6 s never bite at CI token counts).  Per stage we record:

  * ``offered_rate``    — the stage's Poisson arrival rate (req/s)
  * ``throughput_rps``  — completed requests / stage wall time
  * ``goodput_rps``     — on-time completions / stage wall time (a
                          completion counts when its deadline, if any,
                          was met; best-effort completions always count)
  * ``p50_s/p95_s/p99_s`` — service-delay percentiles (completed only)
  * ``deadline_miss_rate``, ``abandoned``, ``weighted_goodput``

The SATURATION STAGE is where goodput peaks: past it, extra offered load
only converts into deadline misses and watchdog shedding, so goodput is
expected to be monotone non-increasing from there on — the invariant the
CI smoke asserts on ``BENCH_stress.json``.  ``saturation_rate`` reports
the offered rate at that knee.

Run it:  PYTHONPATH=src python -m benchmarks.run --only stress --out-dir .
"""
from __future__ import annotations

import time
from typing import List, Optional, Sequence

import numpy as np

from repro.cluster import (EdgeCluster, make_scheduler, poisson_trace,
                           summarize)
from repro.faults import RetryPolicy
from repro.serving.builders import build_fleet, warmup
from repro.workload import scaled

from benchmarks.serving import FLEET_ARCHS, bench_qos_mix


def _on_time(r) -> bool:
    return r.status == "ok" and not bool(r.missed)


def stress_qos_mix(gen_tokens: int, prompt_len: int,
                   deadlines=(0.4, 1.2)):
    """The serving QoS mix with deadlines tightened to the stress run's
    time scale (interactive, standard); batch stays deadline-free."""
    tight = {"interactive": deadlines[0], "standard": deadlines[1]}
    return tuple(
        (scaled(cls, deadline_s=tight[cls.name]) if cls.name in tight
         else cls, w)
        for cls, w in bench_qos_mix(gen_tokens, prompt_len=prompt_len))


def run_stage(engines, scheduler_name: str, *, rate: float,
              num_requests: int, prompt_len: int, gen_tokens: int,
              vocab: int, mix, seed: int) -> dict:
    """One (scheduler, offered-rate) stage on a freshly reset fleet."""
    E = len(engines)
    for e in engines:
        e.reset()
    cluster = EdgeCluster(engines, make_scheduler(scheduler_name, E,
                                                  qos=True),
                          seed=seed, retry=RetryPolicy())
    trace = poisson_trace(num_requests, rate=rate, prompt_len=prompt_len,
                          max_new_tokens=gen_tokens, vocab_size=vocab,
                          num_origins=E, seed=seed, qos_mix=mix)
    t0 = time.monotonic()
    done = cluster.run(trace)
    wall = time.monotonic() - t0
    stats = summarize(done)
    on_time = sum(_on_time(r) for r in done)
    return {
        "offered_rate": float(rate),
        "wall_s": wall,
        "throughput_rps": stats["completed"] / max(wall, 1e-9),
        "goodput_rps": on_time / max(wall, 1e-9),
        "on_time": int(on_time),
        **{k: stats[k] for k in ("count", "completed", "abandoned",
                                 "failed", "p50_s", "p95_s", "p99_s",
                                 "mean_s", "deadline_miss_rate",
                                 "weighted_goodput",
                                 "prefill_tokens_saved",
                                 "prefix_hit_rate")},
    }


def detect_saturation(goodputs: Sequence[float]) -> int:
    """Stage index where goodput peaks — the saturation knee.

    Past the knee, added offered load only buys deadline misses and
    shedding, so goodput must not climb again (the CI invariant)."""
    return int(np.argmax(np.asarray(goodputs, np.float64)))


def bench_stress(scale: str = "quick", n_edge: int = 4,
                 rates: Optional[Sequence[float]] = None,
                 num_requests: Optional[int] = None,
                 window_s: Optional[float] = None,
                 prompt_len: int = 16, gen_tokens: int = 6,
                 seed: int = 0, kv_slots: int = 2,
                 prefill_chunk: int = 8,
                 schedulers: Optional[Sequence[str]] = None):
    """Ramp-to-saturation stress run; returns (csv_rows, json_records).

    Each stage offers ``rate`` arrivals/s for ``window_s`` seconds, so
    stage size ``~ rate * window_s`` (clamped to [3, cap]); pass
    ``num_requests`` to pin every stage to a fixed size instead."""
    paper = scale == "paper"
    if rates is None:
        rates = ((2.0, 8.0, 32.0, 128.0, 512.0, 2048.0) if paper
                 else (8.0, 32.0, 128.0, 512.0, 2048.0))
    if window_s is None:
        window_s = 1.0 if paper else 0.35
    cap = 512 if paper else 192
    if schedulers is None:
        schedulers = (("jsq", "round-robin", "deadline", "random") if paper
                      else ("jsq", "round-robin"))

    def stage_size(rate: float) -> int:
        if num_requests is not None:
            return int(num_requests)
        return int(max(3, min(round(rate * window_s), cap)))

    mix = stress_qos_mix(gen_tokens, prompt_len,
                         deadlines=(2.0, 6.0) if paper else (0.4, 1.2))
    archs = [FLEET_ARCHS[i % len(FLEET_ARCHS)] for i in range(n_edge)]
    max_len = 3 * (prompt_len + gen_tokens)
    engines = build_fleet(archs, max_len,
                          depths=[2 + (i % 2) for i in range(n_edge)],
                          seed0=1, kv_slots=kv_slots,
                          prefill_chunk=prefill_chunk,
                          max_lanes=4 * kv_slots)
    vocab = min(e.cfg.vocab_size for e in engines)
    # warm EVERY prompt length the QoS mix can emit: dense-engine prefill
    # compiles per prompt shape, so an unwarmed length would bill its
    # compile to whichever stage first drew that class
    for plen in sorted({cls.prompt_len or prompt_len for cls, _ in mix}):
        warmup(engines, plen)

    rows: List[str] = []
    records: List[dict] = []
    for name in schedulers:
        stages = []
        for k, rate in enumerate(rates):
            n_k = stage_size(rate)
            st = run_stage(engines, name, rate=rate, num_requests=n_k,
                           prompt_len=prompt_len, gen_tokens=gen_tokens,
                           vocab=vocab, mix=mix, seed=seed + 101 * k)
            st["stage"] = k
            st["num_requests"] = n_k
            stages.append(st)
            rows.append(
                f"stress/{name}@{rate:g}rps,"
                f"{st['wall_s']/max(n_k,1)*1e6:.0f},"
                f"tput={st['throughput_rps']:.2f}rps;"
                f"goodput={st['goodput_rps']:.2f}rps;"
                f"p50={st['p50_s']:.3f}s;p95={st['p95_s']:.3f}s;"
                f"p99={st['p99_s']:.3f}s;"
                f"miss={st['deadline_miss_rate']:.2f};"
                f"shed={st['abandoned']}")
            records.append({"bench": "stress_stage", "scheduler": name,
                            "fleet": [e.arch_id for e in engines], **st})
        sat = detect_saturation([s["goodput_rps"] for s in stages])
        records.append({
            "bench": "stress_summary", "scheduler": name,
            "window_s": window_s,
            "saturation_stage": sat,
            "saturation_rate": stages[sat]["offered_rate"],
            "peak_goodput_rps": stages[sat]["goodput_rps"],
            "stages": stages,
        })
        rows.append(f"stress_summary/{name},0,"
                    f"saturation_rate={stages[sat]['offered_rate']:g}rps;"
                    f"peak_goodput={stages[sat]['goodput_rps']:.2f}rps")

    return rows, records
