"""Benchmark harness: one bench per paper table/figure.

Prints ``name,us_per_call,derived`` CSV.  With ``--out-dir`` the
closedloop and kernels benches additionally write machine-readable
results (``BENCH_closedloop.json`` / ``BENCH_kernels.json``: per-scheduler
throughput + p50/p95/p99 service delay, per-kernel timings with their
execution mode) for CI artifacts and cross-run comparison.

  PYTHONPATH=src python -m benchmarks.run                # quick scale
  PYTHONPATH=src python -m benchmarks.run --scale paper  # Table-III scale
  PYTHONPATH=src python -m benchmarks.run --only fig5,kernels
  PYTHONPATH=src python -m benchmarks.run --only closedloop,kernels \
      --out-dir bench_out

Mapping to the paper:
  fig5     -> Fig. 5   learning curves + convergence episodes
  fig6a    -> Fig. 6a  delay vs number of tasks
  fig6b    -> Fig. 6b  delay vs ES capacity
  fig7a    -> Fig. 7a  delay vs quality demand z
  fig7b    -> Fig. 7b  delay vs number of BSs
  fig8     -> Fig. 8   denoising steps I / entropy temperature alpha
  tablev   -> Table V  centralized vs distributed serving makespan
  closedloop -> (systems) mixed-QoS Poisson trace through a heterogeneous
              live fleet (paged + dense engines) under LAD-TS vs baselines
              incl. deadline-aware (per-class p50/p95/p99, miss rate,
              priority-weighted goodput)
  chaos    -> (systems) the same trace under fault injection: one hard
              mid-trace crash + recovery per scheduler (completion rate,
              retries, orphan-recovery latency, goodput, KV-leak check)
              plus the fault-enabled simulator's wrong-choice rates
  kernels  -> (systems) Pallas kernel microbenches
  roofline -> (systems) dry-run roofline terms per (arch x shape x mesh)
  stress   -> (systems) saturation ramp: Poisson arrival rate climbs a
              geometric ladder per scheduler until deadline goodput
              collapses (per-stage throughput/goodput + p50/p95/p99,
              saturation knee, overlap-vs-serial stepping A/B);
              writes BENCH_stress.json with --out-dir
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--scale", choices=["quick", "paper"], default="quick")
    ap.add_argument("--only", default=None,
                    help="comma list: fig5,fig6a,fig6b,fig7a,fig7b,fig8,"
                         "tablev,closedloop,chaos,kernels,roofline,stress")
    ap.add_argument("--out-dir", default=None,
                    help="write BENCH_<name>.json result files here")
    ap.add_argument("--trajectory", action="store_true",
                    help="append a per-run summary (git sha, date, knee "
                         "goodput, p95, prefix savings) to "
                         "BENCH_trajectory.json in --out-dir — the "
                         "tracked perf trajectory across PRs")
    args = ap.parse_args()
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    only = set(args.only.split(",")) if args.only else None

    def want(name):
        return only is None or name in only

    all_records = {}

    def emit(name, records):
        all_records[name] = records
        if args.out_dir is None:
            return
        os.makedirs(args.out_dir, exist_ok=True)
        path = os.path.join(args.out_dir, f"BENCH_{name}.json")

        def tolist(o):   # numpy / jax scalars and arrays
            if hasattr(o, "tolist"):
                return o.tolist()
            return str(o)

        with open(path, "w") as f:
            json.dump({"bench": name, "scale": args.scale,
                       "records": records}, f, indent=2, default=tolist)
        print(f"# wrote {path}", file=sys.stderr)

    rows = []
    t0 = time.time()

    if want("fig5"):
        from benchmarks.scheduling import bench_fig5_learning
        r, _ = bench_fig5_learning(args.scale)
        rows += r
    if want("fig6a"):
        from benchmarks.scheduling import bench_sweep
        vals = (10, 30, 50, 70) if args.scale == "paper" else (4, 8, 12)
        rows += bench_sweep(args.scale, "max_tasks", vals)
    if want("fig6b"):
        from benchmarks.scheduling import bench_sweep
        vals = (30, 50, 70) if args.scale == "paper" else (20, 40)
        rows += bench_sweep(args.scale, "f_hi", vals)
    if want("fig7a"):
        from benchmarks.scheduling import bench_sweep
        vals = (5, 10, 15, 20) if args.scale == "paper" else (5, 15)
        rows += bench_sweep(args.scale, "z_hi", vals)
    if want("fig7b"):
        from benchmarks.scheduling import bench_sweep
        vals = (10, 20, 30, 40) if args.scale == "paper" else (4, 8)
        rows += bench_sweep(args.scale, "num_bs", vals)
    if want("fig8"):
        from benchmarks.scheduling import bench_fig8_params
        rows += bench_fig8_params(args.scale)
    if want("tablev"):
        from benchmarks.serving import bench_tablev
        rows += bench_tablev()
    if want("closedloop"):
        from benchmarks.serving import bench_closed_loop
        r, recs = bench_closed_loop(args.scale)
        rows += r
        emit("closedloop", recs)
    if want("chaos"):
        from benchmarks.serving import bench_chaos
        r, recs = bench_chaos(args.scale)
        rows += r
        emit("chaos", recs)
    if want("kernels"):
        from benchmarks.kernels import bench_kernels
        r, recs = bench_kernels()
        rows += r
        emit("kernels", recs)
    if want("roofline"):
        from benchmarks.roofline import bench_roofline
        rows += bench_roofline()
    if want("stress"):
        from benchmarks.stress import bench_stress
        r, recs = bench_stress(args.scale)
        rows += r
        emit("stress", recs)

    print("name,us_per_call,derived")
    for r in rows:
        print(r)
    print(f"# total bench wall time: {time.time()-t0:.1f}s",
          file=sys.stderr)

    if args.trajectory:
        if args.out_dir is None:
            ap.error("--trajectory requires --out-dir")
        append_trajectory(args.out_dir, args.scale, all_records)


def _git_sha() -> str:
    import subprocess
    try:
        return subprocess.run(
            ["git", "rev-parse", "--short", "HEAD"],
            capture_output=True, text=True, timeout=10,
            cwd=os.path.dirname(os.path.abspath(__file__)),
        ).stdout.strip() or "unknown"
    except Exception:   # noqa: BLE001 — no git in the environment
        return "unknown"


def append_trajectory(out_dir: str, scale: str, all_records: dict) -> None:
    """Append this run's headline numbers to ``BENCH_trajectory.json``.

    The trajectory is the repo's perf record ACROSS commits: each entry
    carries the git sha + date and, per scheduler, the stress knee
    goodput (+ p95 at the knee) and the closed-loop mean/p95 with prefix
    cache savings — enough to spot a regression or an improvement
    between any two PRs without rerunning history.
    """
    entry = {"git_sha": _git_sha(), "scale": scale,
             "date_utc": time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime()),
             "benches": sorted(all_records)}
    stress = [r for r in all_records.get("stress", [])
              if r.get("bench") == "stress_stage"]
    if stress:
        knees = {}
        for name in sorted({r["scheduler"] for r in stress}):
            stages = [r for r in stress if r["scheduler"] == name]
            knee = max(stages, key=lambda r: r["goodput_rps"])
            knees[name] = {"knee_goodput_rps": knee["goodput_rps"],
                           "knee_offered_rate": knee["offered_rate"],
                           "knee_p95_s": knee["p95_s"]}
        entry["stress"] = knees
    closed = [r for r in all_records.get("closedloop", [])
              if r.get("bench") == "closedloop_live"]
    if closed:
        entry["closedloop"] = {
            r["scheduler"]: {"mean_s": r["mean_s"], "p95_s": r["p95_s"],
                             "prefill_tokens_saved":
                                 r.get("prefill_tokens_saved", 0),
                             "prefix_hit_rate":
                                 r.get("prefix_hit_rate", 0.0)}
            for r in closed}
    chaos = [r for r in all_records.get("chaos", [])
             if r.get("bench") == "chaos_live"]
    if chaos:
        entry["chaos"] = {r["scheduler"]: r["completion_rate"]
                          for r in chaos}

    path = os.path.join(out_dir, "BENCH_trajectory.json")
    doc = {"bench": "trajectory", "runs": []}
    if os.path.exists(path):
        try:
            with open(path) as f:
                prev = json.load(f)
            if isinstance(prev.get("runs"), list):
                doc = prev
        except (OSError, ValueError):
            pass            # corrupt trajectory: restart it, don't crash
    doc["runs"].append(entry)
    with open(path, "w") as f:
        json.dump(doc, f, indent=2)
    print(f"# appended run {entry['git_sha']} to {path} "
          f"({len(doc['runs'])} runs)", file=sys.stderr)


if __name__ == "__main__":
    main()
