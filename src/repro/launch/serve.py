"""Serving launcher: a DEdgeAI-style edge cluster of model replicas.

Replays a Poisson arrival trace through N continuous-batching engines,
with a pluggable scheduler placing each request:

  PYTHONPATH=src python -m repro.launch.serve --arch qwen2-1.5b \
      --edges 2 --scheduler jsq --requests 8 --tokens 16 --rate 4

``--scheduler lad-ts`` first trains the paper policy in the
``repro.core.env`` simulator (matching the engine count), then serves
with it — the closed loop of paper Fig. 10.

Engines serve the ``reduced()`` CPU-sized variant of ``--arch`` at
heterogeneous depths unless ``--full-scale`` asks for the published
config as it stands (its own depth and widths, bf16, scan over layers:
what a TPU serves).  An engine that raises is quarantined so the rest
of the cluster keeps serving, but without ``--chaos`` any quarantine
makes the launcher exit non-zero.

``--qos`` switches on the heterogeneous-QoS workload layer
(``repro.workload``): the trace mixes interactive / standard / batch
service classes, engines drain in priority/EDF order, learned policies
train on the extended observation (deadline slack + per-engine
affinity), ``--scheduler deadline`` becomes available, and the summary
adds deadline-miss rate and priority-weighted goodput.

``--chaos`` switches on the fault layer (``repro.faults``): a
deterministic-per-``--fault-seed`` schedule crashes / stalls / slows
engines mid-trace, orphaned requests are retried with backoff, learned
policies train against the fault-enabled simulator (availability
observation + wrong-choice penalty), ``--scheduler failure-aware``
masks DOWN engines, and the summary adds the terminal-status breakdown
(completed / failed / abandoned, retries, orphan-recovery latency).
"""
from __future__ import annotations

import argparse

import jax

from repro.cluster import (EdgeCluster, PolicyScheduler, make_scheduler,
                           poisson_trace, summarize)
from repro.cluster.schedulers import BASELINES
from repro.configs import get_config, reduced
from repro.core.agents import AgentConfig
from repro.core.diffusion import DiffusionPolicyConfig
from repro.core.env import EnvParams
from repro.core.trainer import LEARNED, train_method
from repro.faults import FaultInjector, FaultParams, FaultSpec, RetryPolicy
from repro.launch.compile_cache import enable_compile_cache
from repro.serving.builders import build_engines, warmup
from repro.workload import DEFAULT_MIX


def build_scheduler(name: str, n_edge: int, train_episodes: int, seed: int,
                    qos: bool = False, chaos: bool = False):
    if name == "deadline" and not qos:
        raise SystemExit("--scheduler deadline needs the QoS-extended "
                         "observation; pass --qos")
    if name == "failure-aware":
        return make_scheduler(name, n_edge, qos=qos)
    if name == "prefix-affinity":
        return make_scheduler(name, n_edge, qos=qos, fault=chaos)
    if name in BASELINES:
        return make_scheduler(name, n_edge)
    if name not in LEARNED:
        raise SystemExit(f"unknown scheduler {name!r}; options: "
                         f"{', '.join(BASELINES + LEARNED)}")
    p = EnvParams(num_bs=n_edge, num_slots=8, max_tasks=6,
                  qos_mix=DEFAULT_MIX if qos else (),
                  fault=FaultParams() if chaos else None)
    acfg = AgentConfig(train_after=40, replay_capacity=200,
                       diffusion=DiffusionPolicyConfig(num_steps=3))
    print(f"[serve] training {name} in-sim for {train_episodes} episodes "
          f"({n_edge} edge servers"
          f"{', fault-enabled' if chaos else ''})...")
    _, states = train_method(name, p, acfg, episodes=train_episodes,
                             key=jax.random.key(seed))
    return PolicyScheduler(name, acfg, states, num_engines=n_edge,
                           n_max=p.max_tasks)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--edges", type=int, default=2)
    ap.add_argument("--scheduler", default="jsq",
                    help="jsq | round-robin | random | local | deadline | "
                         "prefix-affinity | failure-aware | "
                         "lad-ts | d2sac-ts | sac-ts | dqn-ts")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--rate", type=float, default=4.0,
                    help="Poisson arrival rate (req/s)")
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--tokens", type=int, default=16)
    ap.add_argument("--kv-slots", type=int, default=4)
    ap.add_argument("--train-episodes", type=int, default=3)
    ap.add_argument("--qos", action="store_true",
                    help="mixed interactive/standard/batch QoS trace + "
                         "extended scheduler observation")
    ap.add_argument("--chaos", action="store_true",
                    help="inject a deterministic fault schedule (one "
                         "crash + one slowdown) and retry orphans")
    ap.add_argument("--fault-seed", type=int, default=0,
                    help="seed for the --chaos fault schedule")
    ap.add_argument("--prefix-len", type=int, default=0,
                    help="share a seeded system-prompt prefix of this many "
                         "tokens across --prefix-frac of the trace (paged "
                         "engines serve repeats from the prefix cache)")
    ap.add_argument("--prefix-frac", type=float, default=0.75)
    ap.add_argument("--no-prefix-cache", action="store_true",
                    help="disable the paged engines' prefix cache")
    ap.add_argument("--full-scale", action="store_true",
                    help="serve the published config (its own depth, "
                         "widths and dtype) instead of the reduced one")
    ap.add_argument("--sample", action="store_true")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    enable_compile_cache()

    max_tokens = (max(args.tokens,
                      *(c.z_range[1] for c, _ in DEFAULT_MIX))
                  if args.qos else args.tokens)
    cfg = get_config(args.arch)
    if not args.full_scale:
        cfg = reduced(cfg)
    engines = build_engines(args.arch, args.edges,
                            args.prompt_len + max_tokens + cfg.vision_patches,
                            kv_slots=args.kv_slots, sample=args.sample,
                            prefix_cache=(False if args.no_prefix_cache
                                          else None),
                            full_scale=args.full_scale)
    cfg0 = engines[0].cfg
    vocab = cfg0.vocab_size
    warmup(engines, args.prompt_len)       # compile before timed serving

    scheduler = build_scheduler(args.scheduler, args.edges,
                                args.train_episodes, args.seed,
                                qos=args.qos, chaos=args.chaos)
    injector = retry = None
    if args.chaos:
        # horizon = expected trace span + service tail headroom
        horizon = args.requests / max(args.rate, 1e-9) + 2.0
        injector = FaultInjector.from_spec(
            FaultSpec(crashes=1, slowdowns=1), args.edges,
            horizon_s=horizon, seed=args.fault_seed)
        retry = RetryPolicy()
        for ev in injector.describe():
            print(f"[serve] fault @{ev['t_s']:.2f}s engine={ev['engine']} "
                  f"{ev['kind']}")
    cluster = EdgeCluster(engines, scheduler, seed=args.seed,
                          qos_obs=args.qos, faults=injector, retry=retry)
    trace = poisson_trace(args.requests, rate=args.rate,
                          prompt_len=args.prompt_len,
                          max_new_tokens=args.tokens, vocab_size=vocab,
                          num_origins=args.edges, seed=args.seed,
                          num_codebooks=cfg0.num_codebooks,
                          qos_mix=DEFAULT_MIX if args.qos else None,
                          prefix_len=args.prefix_len,
                          prefix_frac=args.prefix_frac)
    if cfg0.vision_patches:
        for r in trace:
            r.patches = jax.random.normal(
                jax.random.fold_in(jax.random.key(args.seed), r.rid),
                (1, cfg0.vision_patches, cfg0.vision_dim))
    done = cluster.run(trace)
    for r in sorted(done, key=lambda r: r.rid):
        if not r.done:          # failed / abandoned: no timestamps
            print(f"[serve] req {r.rid}: {r.status} ({r.fail_reason})")
            continue
        tps = (f"tok/s={len(r.tokens)/r.decode_s:.1f}"
               if r.decode_s > 0 else "tok/s=n/a")
        retried = f" attempts={r.attempts}" if r.attempts > 1 else ""
        print(f"[serve] req {r.rid}: engine={r.engine_id} "
              f"queue={r.queue_s*1e3:.1f}ms "
              f"prefill={r.prefill_s*1e3:.1f}ms "
              f"decode={r.decode_s*1e3:.1f}ms "
              f"service={r.service_s*1e3:.1f}ms {tps}{retried}")
    st = summarize(done)
    line = (f"[serve] {scheduler.name}: n={st['count']} "
            f"mean={st['mean_s']*1e3:.1f}ms p95={st['p95_s']*1e3:.1f}ms "
            f"max={st['max_s']*1e3:.1f}ms")
    if st["prefill_tokens_saved"]:
        line += (f" prefix_saved={st['prefill_tokens_saved']}tok"
                 f" hit={st['prefix_hit_rate']:.2f}")
    if args.chaos:
        fs = cluster.fault_stats
        line += (f" cr={st['completion_rate']:.3f}"
                 f" retries={st['retries']}"
                 f" failed={st['failed']} abandoned={st['abandoned']}"
                 f" orphans={fs['orphaned']}")
    if args.qos:
        line += (f" miss={st['deadline_miss_rate']:.2f}"
                 f" goodput={st['weighted_goodput']:.2f}")
        for name, cs in st.get("classes", {}).items():
            print(f"[serve]   class {name}: n={cs['count']} "
                  f"p50={cs['p50_s']*1e3:.1f}ms "
                  f"p95={cs['p95_s']*1e3:.1f}ms "
                  f"miss={cs['deadline_miss_rate']:.2f}")
    print(line)
    quarantined = cluster.fault_stats["quarantined"]
    if quarantined and not args.chaos:
        raise SystemExit(f"[serve] {quarantined} engine(s) quarantined "
                         f"after raising; see the failed requests above")


if __name__ == "__main__":
    main()
