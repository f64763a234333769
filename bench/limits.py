#!/usr/bin/env python3
"""Readings that a cell's check limits are set from, in one process.

For each program seed: the cell's set-up, a window of ``--seconds``, and
the numbers the check compares (the program's answers against the plain
reference): the lower readings. For each control seed, the same, and then
the control's numbers: the plain reference computed in the precision
below the configuration's, put in the program's place and compared with
the reference: the upper readings. The benchmark's own runs never run
the control.

Usage (on the chip, from the root of a checkout):

    python3 bench/limits.py --workload search.cardio --seconds 6 \
        --seeds 101,102,103 --control-seeds 101,102,103 \
        --out limits_search.cardio.json
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(ROOT / "src"))
sys.path.insert(0, str(ROOT))


def readings(workload: str, seed: int, seconds: float, control: bool,
             *, require_tpu: bool = True, traffic_override=None) -> dict:
    from bench.lib import harness
    run = harness.build_run(ROOT, workload, seed, seconds, False,
                            time.perf_counter(), require_tpu=require_tpu,
                            traffic_override=traffic_override)
    driver = harness.load_module(
        harness.driver_file(ROOT, run.traffic["driver"]), "driver")
    state = driver.setup(run)
    out = driver.window(run, state)
    ref = driver.reference(run, state, out, control=False)
    got = driver.answers(run, state, out)
    row = {"seed": seed, "program": driver.compare(run, state, got, ref)}
    if control:
        ctl = driver.reference(run, state, out, control=True)
        row["control"] = driver.compare(run, state, ctl, ref)
    return row


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", default="")
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--out", required=True)
    args = ap.parse_args(argv)
    seeds = [int(s) for s in args.seeds.split(",") if s]
    ctl = {int(s) for s in args.control_seeds.split(",") if s}
    rows = []
    for seed in seeds + sorted(ctl - set(seeds)):
        rows.append(readings(args.workload, seed, args.seconds, seed in ctl))
        print(json.dumps(rows[-1]), flush=True)
        Path(args.out).write_text(json.dumps(rows, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
