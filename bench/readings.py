#!/usr/bin/env python3
"""Read the numbers a cell's correctness limits are set from, on the chip.

    python3 bench/readings.py --workload <name> --seeds 12 --control 3 \
        [--fault half_batch --fault-seeds 3] [--seconds 10] > readings.jsonl

In one process (set-up is long): the program's compared numbers on
``--seeds`` seeds through the cell's own run (``--seconds`` of window at the
cell's load), the control's (the reference in fp8 put in the program's
place) on the first ``--control`` of them, and a planted fault's on
``--fault-seeds`` more.  One JSON line per run; ``PERF.md`` gives, for each
limit, the readings it was set from.  The benchmark's own runs never run it.
"""

import argparse
import gc
import json
import os
import sys
import time

sys.path[0] = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

from bench import harness  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--control", type=int, default=3)
    ap.add_argument("--fault", default=None)
    ap.add_argument("--fault-seeds", type=int, default=3)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--first-seed", type=int, default=3_000_000_000)
    args = ap.parse_args(argv)
    runs = [(args.first_seed + i, i < args.control, None) for i in range(args.seeds)]
    runs += [(args.first_seed + 1000 + i, False, args.fault)
             for i in range(args.fault_seeds if args.fault else 0)]
    for seed, control, fault in runs:
        t0 = time.perf_counter()
        outs: list = []
        result = harness.run_cell(args.workload, seed, args.seconds, False,
                                  fault=fault, control=control, outcome=outs)
        low = outs[0].notes.get("control")
        # the control put in the program's place, held to the cell's own limits
        control_correct = None
        if low is not None:
            control_correct = harness.Outcome(
                end_to_end={}, layer={}, attempted=0, failed=0, memory_peak_bytes=0,
                checks=[{"name": k, "value": v, "limit": result["checks"][k]["limit"]}
                        for k, v in low.items()],
            ).correct
        print(json.dumps({
            "seed": seed, "fault": fault, "correct": result["correct"],
            "checks": {k: v["value"] for k, v in result["checks"].items()},
            "control": low, "control_correct": control_correct,
            "memory_peak_bytes": result["device"]["memory_peak_bytes"],
            "metrics": {k: v["value"] for k, v in result["metrics"].items()},
            "seconds": time.perf_counter() - t0,
        }), flush=True)
        del outs
        gc.collect()
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
