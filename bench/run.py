"""The benchmark's one command.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Runs one cell of ``BENCHMARK.json`` on the chips of this machine: set-up
(weights from the seed, warm-up, scheduler), ``--seconds`` of measured
traffic, then the check against the plain reference.  The last line of
standard output is the result object; the numbers compared, each beside
its limit, are the last lines of standard error.  ``--trace 1`` profiles
a slice of the window and reports the per-layer metrics instead of the
end-to-end ones.  Without a TPU, or with fewer chips than the cell asks
for, it exits non-zero and prints no result.

``--rate`` overrides an open-loop mix's request rate (for
``bench/sweep.py`` only).
"""
from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parents[1]


def parse(argv=None) -> argparse.Namespace:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rate", type=float, default=None)
    return ap.parse_args(argv)


def main(argv=None) -> int:
    args = parse(argv)
    for p in (ROOT / "src", ROOT):
        if str(p) not in sys.path:
            sys.path.insert(0, str(p))
    from bench import harness
    try:
        result = harness.run(ROOT, args.workload, args.seed, args.seconds,
                             bool(args.trace), T_PROCESS, rate=args.rate)
    except ImportError as e:
        print(f"bench: cannot import the system under test: {e}",
              file=sys.stderr)
        return 2
    except harness.Refused as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
