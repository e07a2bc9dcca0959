"""Run one benchmark cell on the accelerator this process holds.

Usage:
  python perf/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The last line of standard output is one JSON object (``correct``,
``attempted``, ``failed``, ``metrics``, ``device`` and, with ``--trace 1``,
``breakdown``). Without a TPU, with fewer chips than the cell asks for, or
outside a checkout that holds the program (``src/repro``), the script
exits non-zero and prints no result line.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()          # set-up is timed from here

import argparse  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if args.seed < 0:
        print("run: --seed must be a whole number", file=sys.stderr)
        return 2
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    try:
        import repro  # noqa: F401  (the system under test)
    except ImportError:
        print("run: the program (src/repro) is not in this checkout",
              file=sys.stderr)
        return 2
    from perf import harness
    return harness.main(args.workload, seed=args.seed, seconds=args.seconds,
                        trace=bool(args.trace), t_start=T_START)


if __name__ == "__main__":
    sys.exit(main())
