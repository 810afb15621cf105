"""Serving benchmarks on the cluster API.

``bench_tablev``       — Table-V analog: total generation delay, centralized
                         vs DEdgeAI-style distributed serving, smoke scale.
``bench_closed_loop``  — the repo's apples-to-apples "paper policy vs
                         baselines on real engines" number, now on a
                         HETEROGENEOUS fleet under a mixed-QoS workload:
                         a Poisson trace of interactive / standard /
                         batch requests replayed through engines hosting
                         DIFFERENT model-zoo configs (attention models
                         on the paged KV pool next to dense-slot xLSTM),
                         reporting per-scheduler AND per-QoS-class
                         p50/p95/p99 service delay, deadline-miss rate,
                         and priority-weighted goodput (CSV rows + JSON
                         records), plus the same schedulers evaluated in
                         the ``core.env`` simulator on the identical
                         extended Eqn-6 observation.
``bench_chaos``        — goodput under failures: the same mixed-QoS trace
                         replayed per scheduler while a deterministic
                         fault schedule crashes one engine mid-trace and
                         recovers it; reports completion rate, retries,
                         orphan-recovery latency, priority-weighted
                         goodput and the KV-accounting invariant, plus
                         the fault-enabled simulator's wrong-choice rate.
"""
from __future__ import annotations

import time
from typing import List

import jax
import numpy as np

from repro.cluster import (EdgeCluster, PolicyScheduler, evaluate_scheduler,
                           make_scheduler, poisson_trace, summarize)
from repro.configs import get_config, reduced
from repro.core.agents import AgentConfig
from repro.core.diffusion import DiffusionPolicyConfig
from repro.core.env import EnvParams
from repro.core.trainer import train_method
from repro.faults import FaultParams, RetryPolicy, single_crash
from repro.serving.builders import build_engines, build_fleet, warmup
from repro.workload import BEST_EFFORT, INTERACTIVE, STANDARD, scaled

# Default heterogeneous fleet for the closed loop: two arch families
# (attention -> paged KV pool, xLSTM -> dense slot pool) at different
# parameter scales, cycled over the edge servers.
FLEET_ARCHS = ("qwen2-1.5b", "starcoder2-3b", "xlstm-350m")


def bench_tablev(num_requests=(1, 8, 32), prompt_len: int = 16,
                 gen_tokens: int = 8, n_edge: int = 4) -> List[str]:
    """Centralized (one deep engine) vs edge cluster (n_edge shallow
    engines + JSQ placement), makespan per request count."""
    max_len = prompt_len + gen_tokens
    cloud = build_engines("qwen2-1.5b", 1, max_len, depths=[4])[0]
    edges = build_engines("qwen2-1.5b", n_edge, max_len,
                          depths=[2 + (i % 2) for i in range(n_edge)],
                          seed0=1)
    vocab = reduced(get_config("qwen2-1.5b")).vocab_size
    warmup([cloud] + edges, prompt_len)

    rows = []
    for N in num_requests:
        def trace():
            return poisson_trace(N, rate=1e6, prompt_len=prompt_len,
                                 max_new_tokens=gen_tokens,
                                 min_new_tokens=gen_tokens,
                                 vocab_size=vocab, num_origins=n_edge,
                                 seed=N)

        # centralized: every request through the single cloud engine
        cloud.reset()
        central = EdgeCluster([cloud], make_scheduler("round-robin", 1))
        t0 = time.monotonic()
        stats_c = summarize(central.run(trace()))
        wall_cloud = time.monotonic() - t0

        # distributed: queue-aware placement over the edge cluster
        for e in edges:
            e.reset()
        edge = EdgeCluster(edges, make_scheduler("jsq", n_edge))
        t0 = time.monotonic()
        stats_e = summarize(edge.run(trace()))
        wall_edge = time.monotonic() - t0

        speedup = wall_cloud / max(wall_edge, 1e-9)
        rows.append(
            f"tableV_N={N}/centralized,{wall_cloud/max(N,1)*1e6:.0f},"
            f"mean={stats_c['mean_s']:.3f}s;p95={stats_c['p95_s']:.3f}s")
        rows.append(
            f"tableV_N={N}/dedgeai,{wall_edge/max(N,1)*1e6:.0f},"
            f"mean={stats_e['mean_s']:.3f}s;p95={stats_e['p95_s']:.3f}s;"
            f"speedup={speedup:.2f}x")
    return rows


def bench_qos_mix(gen_tokens: int, prompt_len: int = 0):
    """QoS mix rescaled to the benchmark's token scale: interactive
    requests are short (half-length prompts) and prefer the smallest
    model, batch requests carry double-length prompts and run up to 3x
    the nominal generation length with no deadline.  ``prompt_len=0``
    keeps the trace-level prompt length for every class."""
    plens = {c: None for c in ("interactive", "standard", "batch")}
    if prompt_len:
        plens = {"interactive": max(prompt_len // 2, 1),
                 "standard": None,          # trace-level default
                 "batch": 2 * prompt_len}
    return ((scaled(INTERACTIVE, z_range=(1, gen_tokens),
                    prompt_len=plens["interactive"],
                    model_pref="xlstm-350m"), 0.4),
            (scaled(STANDARD,
                    z_range=(max(gen_tokens // 2, 1), 2 * gen_tokens)), 0.4),
            (scaled(BEST_EFFORT, prompt_len=plens["batch"],
                    z_range=(gen_tokens, 3 * gen_tokens)), 0.2))


def bench_closed_loop(scale: str = "quick", n_edge: int = 4,
                      num_requests: int = 24, rate: float = 96.0,
                      prompt_len: int = 32, gen_tokens: int = 8,
                      seed: int = 0, kv_slots: int = 2,
                      prefill_chunk: int = 16, page_size: int = 8,
                      prefix_len: int = -1, prefix_frac: float = 0.75):
    """Closed loop: train LAD-TS in the QoS-enabled sim, then replay one
    mixed-class Poisson trace through a HETEROGENEOUS live fleet under
    the paper policy and each baseline (including deadline-aware and the
    cache-aware prefix-affinity router).

    The fleet cycles ``FLEET_ARCHS`` over the edge servers, so paged
    attention engines and dense-slot xLSTM engines serve side by side;
    each engine queue drains in priority/EDF order.  The schedulers see
    the extended Eqn-6 observation ``[d, w, q_1..q_E, slack, c_1..c_E]``
    in BOTH backends, and every record carries the per-QoS-class
    breakdown (p50/p95/p99, deadline-miss rate, priority-weighted
    goodput).

    The trace is a shared-system-prompt mix: ``prefix_frac`` of requests
    open with one common seeded prefix (``prefix_len`` tokens, default
    3/4 of the prompt; truncated per class to its own prompt length), so
    paged engines with prefix caching serve most repeat prompts without
    re-prefilling — records report ``prefill_tokens_saved`` and
    ``prefix_hit_rate`` per scheduler.  Pass ``prefix_len=0`` for the
    legacy prefix-free trace (bit-identical behavior).

    Returns (csv_rows, json_records)."""
    paper = scale == "paper"
    if prefix_len < 0:
        prefix_len = (3 * prompt_len) // 4
    # per-class prompt lengths: interactive half-length, batch double —
    # the live fleet sees a mixed prompt-length distribution (the sim's
    # d_n spread already models it); max_len=3*(prompt+gen) below covers
    # the worst case 2*prompt_len + 3*gen_tokens
    mix = bench_qos_mix(gen_tokens, prompt_len=prompt_len)
    p = EnvParams(num_bs=n_edge, num_slots=30 if paper else 8,
                  max_tasks=12 if paper else 6, qos_mix=mix)
    acfg = AgentConfig(train_after=120 if paper else 40,
                       replay_capacity=500 if paper else 200,
                       diffusion=DiffusionPolicyConfig(
                           num_steps=5 if paper else 3))
    episodes = 20 if paper else 3
    _, states = train_method("lad-ts", p, acfg, episodes=episodes,
                             key=jax.random.key(seed))

    def scheds():
        return {
            "lad-ts": PolicyScheduler("lad-ts", acfg, states, p.layout,
                                      n_max=p.max_tasks),
            **{name: make_scheduler(name, n_edge, qos=True)
               for name in ("deadline", "prefix-affinity", "jsq",
                            "round-robin", "random", "local")},
        }

    def qos_suffix(stats):
        return (f";miss={stats.get('deadline_miss_rate', 0.0):.2f}"
                f";goodput={stats.get('weighted_goodput', 0.0):.2f}")

    rows, records = [], []
    # --- same Scheduler interface against the core.env simulator ----------
    for name, s in scheds().items():
        if s.layout.prefix:
            continue   # the slot-based sim has no KV model to be warm in
        t0 = time.monotonic()
        r = evaluate_scheduler(s, p, episodes=2, key=jax.random.key(1))
        r.pop("carry", None)   # agent pytree, not JSON material
        wall = time.monotonic() - t0
        us = wall / max(r["count"], 1) * 1e6
        rows.append(f"closedloop_sim/{name},{us:.0f},"
                    f"mean={r['mean_s']:.3f}s;p95={r['p95_s']:.3f}s"
                    + qos_suffix(r))
        records.append({"bench": "closedloop_sim", "scheduler": name,
                        "wall_s": wall, **r})

    # --- and against the live heterogeneous fleet ---------------------------
    archs = [FLEET_ARCHS[i % len(FLEET_ARCHS)] for i in range(n_edge)]
    # engines are provisioned for requests up to max_len; batch-class
    # requests generate up to 3 * gen_tokens, and the paged engines keep
    # pooling whatever KV the short interactive requests leave free
    max_len = 3 * (prompt_len + gen_tokens)
    engines = build_fleet(archs, max_len,
                          depths=[2 + (i % 2) for i in range(n_edge)],
                          seed0=1, kv_slots=kv_slots,
                          prefill_chunk=prefill_chunk,
                          page_size=page_size,
                          max_lanes=4 * kv_slots)
    # one trace must tokenize for every engine in the mixed fleet
    vocab = min(e.cfg.vocab_size for e in engines)
    warmup(engines, prompt_len)
    for name, s in scheds().items():
        for e in engines:
            e.reset()             # also clears each prefix cache: every
        # scheduler starts COLD, so hit rates compare placement quality
        cluster = EdgeCluster(engines, s, seed=seed)
        trace = poisson_trace(num_requests, rate=rate,
                              prompt_len=prompt_len,
                              max_new_tokens=gen_tokens,
                              vocab_size=vocab,
                              num_origins=n_edge, seed=seed + 1,
                              qos_mix=mix, prefix_len=prefix_len,
                              prefix_frac=prefix_frac)
        t0 = time.monotonic()
        stats = summarize(cluster.run(trace))
        wall = time.monotonic() - t0
        us = wall / max(stats["count"], 1) * 1e6
        peak = max(e.peak_inflight for e in engines)
        rows.append(f"closedloop_live/{name},{us:.0f},"
                    f"mean={stats['mean_s']:.3f}s;"
                    f"p50={stats['p50_s']:.3f}s;"
                    f"p95={stats['p95_s']:.3f}s;"
                    f"p99={stats['p99_s']:.3f}s;"
                    f"peak_inflight={peak}" + qos_suffix(stats)
                    + f";saved={stats['prefill_tokens_saved']}"
                    f";hit={stats['prefix_hit_rate']:.2f}")
        records.append({
            "bench": "closedloop_live", "scheduler": name,
            "wall_s": wall,
            "throughput_rps": stats["count"] / max(wall, 1e-9),
            "fleet": [e.arch_id for e in engines],
            "paged": [bool(e.paged) for e in engines],
            "kv_slots": kv_slots,
            "prefill_chunk": prefill_chunk,
            "prompt_len": prompt_len,
            "page_size": page_size,
            "prefix_len": prefix_len,
            "prefix_frac": prefix_frac,
            "peak_inflight": peak,
            "engine_prefill_tokens_saved": [int(e.prefill_tokens_saved)
                                            for e in engines],
            "engine_prefix_hit_rate": [float(e.prefix_hit_rate)
                                       for e in engines],
            "cow_forks": int(sum(e.cow_forks for e in engines)),
            "prefix_evictions": int(sum(e.prefix_evictions
                                        for e in engines)),
            **stats})
    return rows, records


def bench_chaos(scale: str = "quick", n_edge: int = 2,
                num_requests: int = 16, rate: float = 48.0,
                prompt_len: int = 16, gen_tokens: int = 6,
                seed: int = 0, kv_slots: int = 2, prefill_chunk: int = 8,
                fault_seed: int = 0):
    """Chaos run: one hard mid-trace crash + recovery, per scheduler.

    A calibration pass (JSQ, fault-free) measures the trace makespan;
    the chaos passes then crash one engine at 0.3x that makespan and
    recover it 0.35x later, so every scheduler faces the IDENTICAL
    deterministic fault schedule (same ``fault_seed`` -> same schedule).
    Acceptance: every non-abandoned request completes (completion_rate
    == 1.0), retries stay within the policy cap, and each engine's KV
    accounting returns to zero — the crash-recovery invariants CI
    asserts on the emitted ``BENCH_chaos.json``.

    Returns (csv_rows, json_records)."""
    paper = scale == "paper"
    if paper:
        num_requests, rate = 4 * num_requests, 2 * rate
    mix = bench_qos_mix(gen_tokens, prompt_len=prompt_len)
    E = n_edge
    archs = [FLEET_ARCHS[i % len(FLEET_ARCHS)] for i in range(E)]
    max_len = 3 * (prompt_len + gen_tokens)
    engines = build_fleet(archs, max_len,
                          depths=[2 + (i % 2) for i in range(E)],
                          seed0=1, kv_slots=kv_slots,
                          prefill_chunk=prefill_chunk,
                          max_lanes=4 * kv_slots)
    vocab = min(e.cfg.vocab_size for e in engines)
    warmup(engines, prompt_len)

    def trace():
        return poisson_trace(num_requests, rate=rate,
                             prompt_len=prompt_len,
                             max_new_tokens=gen_tokens, vocab_size=vocab,
                             num_origins=E, seed=seed + 1, qos_mix=mix)

    # --- calibration: fault-free makespan anchors the fault schedule ----
    for e in engines:
        e.reset()
    t0 = time.monotonic()
    EdgeCluster(engines, make_scheduler("jsq", E), seed=seed).run(trace())
    makespan = time.monotonic() - t0
    crash_t = 0.3 * makespan
    downtime = 0.35 * makespan
    rng = np.random.default_rng(fault_seed)
    victim = int(rng.integers(E))

    rows, records = [], []
    scheds = {
        "failure-aware": make_scheduler("failure-aware", E, qos=True),
        "deadline": make_scheduler("deadline", E),
        "jsq": make_scheduler("jsq", E),
        "round-robin": make_scheduler("round-robin", E),
    }
    for name, s in scheds.items():
        for e in engines:
            e.reset()
        inj = single_crash(engine=victim, t_s=crash_t,
                           downtime_s=downtime, num_engines=E)
        cluster = EdgeCluster(engines, s, seed=seed, faults=inj,
                              retry=RetryPolicy())
        t0 = time.monotonic()
        stats = summarize(cluster.run(trace()))
        wall = time.monotonic() - t0
        fs = cluster.fault_stats
        rec_s = fs["orphan_recovery_s"]
        leak = [int(e.kv_leak) for e in engines]
        rows.append(
            f"chaos_live/{name},{wall/max(num_requests,1)*1e6:.0f},"
            f"cr={stats['completion_rate']:.3f};"
            f"completed={stats['completed']};failed={stats['failed']};"
            f"abandoned={stats['abandoned']};retries={stats['retries']};"
            f"orphans={fs['orphaned']};"
            f"goodput={stats.get('weighted_goodput', 0.0):.2f};"
            f"kv_leak={sum(leak)}")
        records.append({
            "bench": "chaos_live", "scheduler": name, "wall_s": wall,
            "makespan_calib_s": makespan,
            "goodput_rps": stats["completed"] / max(wall, 1e-9),
            "fault_schedule": inj.describe(), "fault_seed": fault_seed,
            "orphan_recovery_mean_s": (float(np.mean(rec_s))
                                       if rec_s else 0.0),
            "kv_leak": leak,
            **{k: v for k, v in fs.items() if k != "orphan_recovery_s"},
            **stats})

    # --- fault-enabled simulator twin: wrong-choice rate ----------------
    p = EnvParams(num_bs=E, num_slots=16 if paper else 8,
                  max_tasks=8 if paper else 5,
                  fault=FaultParams(p_down=0.15, p_up=0.5))
    for name in ("failure-aware", "jsq", "round-robin"):
        s = make_scheduler(name, E)
        t0 = time.monotonic()
        r = evaluate_scheduler(s, p, episodes=2, key=jax.random.key(seed))
        r.pop("carry", None)
        wall = time.monotonic() - t0
        rows.append(f"chaos_sim/{name},{wall/max(r['count'],1)*1e6:.0f},"
                    f"mean={r['mean_s']:.3f}s;"
                    f"wrong={r['wrong_choice_rate']:.3f}")
        records.append({"bench": "chaos_sim", "scheduler": name,
                        "wall_s": wall, "p_down": p.fault.p_down,
                        "p_up": p.fault.p_up, **r})
    return rows, records
