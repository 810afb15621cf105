"""End-to-end DEdgeAI driver: a heterogeneous edge cluster serving real
(reduced-config) model inference through the ``repro.cluster`` API.

    PYTHONPATH=src python examples/serve_edge.py --requests 12
    PYTHONPATH=src python examples/serve_edge.py --requests 12 --qos

This is the paper's Fig. 10 worker loop at smoke scale:
  1. N_edge continuous-batching ServeEngines with different depths (speed
     heterogeneity), each running a REAL reduced transformer (per-request
     prefill + slot-pool decode with mid-flight joins).
  2. Requests arrive as a Poisson trace; the pluggable Scheduler
     (join-shortest-queue, round-robin, random, local-only — the same
     interface the trained LAD-TS policy plugs into) picks an ES each.
  3. Reported per-request delay = measured queue + prefill + decode, the
     serving-side terms of Eqn (2).

With ``--qos`` the trace mixes the default interactive / standard /
batch service classes (``repro.workload``): engines drain their queues
in priority/EDF order, the schedulers see the extended observation
(deadline slack + per-engine affinity), the deadline-aware baseline
joins the comparison, and the report adds deadline-miss rate and
priority-weighted goodput.
"""
import argparse
import sys
import time

sys.path.insert(0, "src")

from repro.cluster import (EdgeCluster, make_scheduler,  # noqa: E402
                           poisson_trace, summarize)
from repro.serving.builders import build_engines, warmup  # noqa: E402
from repro.workload import DEFAULT_MIX  # noqa: E402


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--requests", type=int, default=12)
    ap.add_argument("--edges", type=int, default=4)
    ap.add_argument("--arch", default="qwen2-1.5b")
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--tokens", type=int, default=8)
    ap.add_argument("--kv-slots", type=int, default=4)
    ap.add_argument("--rate", type=float, default=8.0)
    ap.add_argument("--qos", action="store_true",
                    help="mixed interactive/standard/batch QoS trace")
    args = ap.parse_args()

    qos_mix = DEFAULT_MIX if args.qos else None
    max_tokens = (max(c.z_range[1] for c, _ in DEFAULT_MIX)
                  if args.qos else args.tokens)
    engines = build_engines(args.arch, args.edges,
                            args.prompt_len + max_tokens,
                            kv_slots=args.kv_slots)
    vocab = engines[0].cfg.vocab_size

    # warm up compiles so timings reflect steady-state serving
    warmup(engines, args.prompt_len)

    policies = ("jsq", "round-robin", "random", "local")
    if args.qos:
        policies += ("deadline",)
    for policy in policies:
        for e in engines:
            e.reset()
        cluster = EdgeCluster(engines, make_scheduler(policy, args.edges,
                                                      qos=args.qos))
        trace = poisson_trace(args.requests, rate=args.rate,
                              prompt_len=args.prompt_len,
                              max_new_tokens=args.tokens,
                              vocab_size=vocab, num_origins=args.edges,
                              seed=42, qos_mix=qos_mix)
        t0 = time.time()
        stats = summarize(cluster.run(trace))
        line = (f"{policy:12s}: mean service delay "
                f"{stats['mean_s']*1e3:7.1f} ms  "
                f"p95 {stats['p95_s']*1e3:7.1f} ms  "
                f"(n={stats['count']}, wall {time.time()-t0:.1f}s)")
        if args.qos:
            line += (f"  miss={stats['deadline_miss_rate']:.2f}"
                     f" goodput={stats['weighted_goodput']:.2f}")
        print(line)


if __name__ == "__main__":
    main()
