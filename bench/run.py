#!/usr/bin/env python3
"""Run one benchmark cell once on the chip and print its result.

Usage (from the root of a checkout):

    python3 bench/run.py --workload search.cardio --seed 7 --seconds 48 \
        --trace 0

The cell, its configuration, traffic mix, driver and metrics are found by
name from ``BENCHMARK.json`` (see ``bench/README.md``). Set-up builds the
system under test from ``--seed`` and warms every shape the window uses;
the window then runs for ``--seconds``; afterwards the program's answers
are compared with a plain reference. The last line of stdout is one JSON
object (``correct``, ``attempted``, ``failed``, ``metrics``, ``device``,
with ``--trace 1`` also ``breakdown``, and last ``checks``); each number
compared is also printed beside its limit as the last lines of stderr.

With ``--trace 0`` the metrics are the cell's end-to-end metrics, with
``--trace 1`` its per-layer metrics, read from a profiler trace of the
first seconds of the window and from the run's spans and counters.

The run exits non-zero and prints no result where JAX finds no TPU, or
fewer chips than the cell asks for.
"""
from __future__ import annotations

import argparse
import sys
import time
from pathlib import Path

T_START = time.perf_counter()
ROOT = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(ROOT / "src"))
sys.path.insert(0, str(ROOT))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        from bench.lib import harness
        run = harness.build_run(ROOT, args.workload, args.seed, args.seconds,
                                bool(args.trace), T_START)
        result = harness.execute(run)
    except Exception as e:                  # noqa: BLE001 — report, exit 1
        import traceback
        traceback.print_exc()
        print(f"bench/run.py: {type(e).__name__}: {e}", file=sys.stderr)
        return 1
    harness.report(result)
    return 0


if __name__ == "__main__":
    sys.exit(main())
