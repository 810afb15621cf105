"""Find the knee of an open-loop cell by a ladder of offered rates.

    python3 bench/sweep.py --workload <cell> --rates 2,4,6,8 --seconds 20

Runs the one command (``bench/run.py``) once per rate, each in its own
process, and prints one JSON line per rate: the offered and delivered
output tokens/s, the median TTFT of the window's first and last thirds,
and the end-to-end metrics.  The knee is the highest rate at which the
server keeps up: no request failed, and the last third's median TTFT at
most twice the first's (a queue that grows through the window fails
this; delivered tokens/s lag offered ones whenever requests last about
as long as the window, so they do not decide).  A cell runs at about
four fifths of the knee.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import subprocess
import sys

ROOT = pathlib.Path(__file__).resolve().parents[1]
LOAD = "[bench] load "


def keeps_up(load: dict) -> bool:
    first, _, last = load["ttft_p50_ms_by_third"]
    return (load["failed"] == 0 and first is not None and last is not None
            and last <= 2.0 * first)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args(argv)
    knee = None
    for rate in (float(r) for r in args.rates.split(",")):
        cmd = [sys.executable, str(ROOT / "bench" / "run.py"),
               "--workload", args.workload, "--seed", str(args.seed),
               "--seconds", str(args.seconds), "--trace", "0",
               "--rate", str(rate)]
        p = subprocess.run(cmd, capture_output=True, text=True, cwd=ROOT)
        loads = [json.loads(ln[len(LOAD):]) for ln in p.stderr.splitlines()
                 if ln.startswith(LOAD)]
        lines = p.stdout.strip().splitlines()
        result = json.loads(lines[-1]) if p.returncode == 0 and lines else {}
        row = {"rate": rate, "rc": p.returncode,
               "load": loads[-1] if loads else None,
               "correct": result.get("correct"),
               "metrics": {k: v["value"]
                           for k, v in result.get("metrics", {}).items()}}
        if row["load"] is not None and keeps_up(row["load"]):
            knee = rate
        if p.returncode:
            row["stderr_tail"] = p.stderr[-2000:]
        print(json.dumps(row), flush=True)
    print(json.dumps({"knee_rate": knee,
                      "cell_rate": None if knee is None else 0.8 * knee}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
