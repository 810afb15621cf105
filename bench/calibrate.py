"""Readings for the limits of the correctness check, on the chip.

    python3 bench/calibrate.py --workload <cell> --seeds 1,2,3 --seconds 12

Runs the cell once per seed in ONE process (the set-up compiles once),
each with a short window at the cell's own load, and compares the
sampled requests with the plain reference twice: the program's served
tokens (the lower reading) and the float8 control's first choices at
the same positions (the upper reading), each through the run's own
checks (``correct``, ``control_correct``).  Prints one JSON line per
seed.
``--mix`` serves another traffic file of the same configuration.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parents[1]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=12.0)
    ap.add_argument("--mix", default=None)
    ap.add_argument("--no-control", action="store_true")
    args = ap.parse_args(argv)
    for p in (ROOT / "src", ROOT):
        sys.path.insert(0, str(p))
    from bench import harness

    for seed in (int(s) for s in args.seeds.split(",")):
        t = time.perf_counter()
        res = harness.run(ROOT, args.workload, seed, args.seconds, False, t,
                          control=not args.no_control, mix=args.mix)
        print(json.dumps({
            "seed": seed, "mix": args.mix, "correct": res["correct"],
            "failed": res["failed"], "attempted": res["attempted"],
            "max_logit_gap": res["checks"]["max_logit_gap"]["value"],
            "control_correct": res.get("control_correct"),
            "control_max_logit_gap": res.get("control_max_logit_gap"),
            "compared_tokens": res["checks"]["compared_tokens"]["value"],
            "seconds_total": time.perf_counter() - t}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
