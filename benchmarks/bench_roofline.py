"""Roofline mini dry-run: the bench-smoke rows gating the analysis pipeline.

Runs a reduced (arch × shape) dry-run matrix — SMOKE_CONFIGs on a forced
8-host-device 2×4 ("data","model") mesh — in a subprocess (jax locks the
device count at first init, so the forced topology must not leak into the
parent), then pushes the artifacts through ``benchmarks.roofline`` exactly as
the full 512-device matrix would be.  Two rows per cell:

    roofline/<arch>/<shape>/bound_us   perfect-overlap step lower bound
                                       (max of compute/memory/collective)
    roofline/<arch>/<shape>/gap        bound / ideal-model-compute time
                                       (dimensionless; 1.0 = at the roofline)

Unlike the timed benches these are *deterministic* — derived from compiled
HLO cost analysis, not wall time — so the CI gate runs them once (no
min-of-3) and any ratio drift against the committed baseline means the
lowered computation itself changed shape (flops, bytes, or collectives).
"""

from __future__ import annotations

import argparse
import json
import os
import platform
import subprocess
import sys
import tempfile
import time

_MINI_SCRIPT = r"""
import os, sys
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
out_dir = sys.argv[1]

import jax
from jax.sharding import AxisType
from repro.launch.dryrun import run_cell
from repro.models.config import ShapeConfig

mesh = jax.make_mesh((2, 4), ("data", "model"), axis_types=(AxisType.Auto,) * 2)
train = ShapeConfig("mini_train", "train", 128, 8)
decode = ShapeConfig("mini_decode", "decode", 256, 8)
cells = [
    ("llama3.2-1b", train),          # dense attention, tied embeddings
    ("recurrentgemma-9b", train),    # hybrid rglru + local-attention pattern
    ("llama3.2-1b", decode),         # memory-bound cell (cache + params)
]
for arch, shape in cells:
    run_cell(arch, shape.name, False, out_dir=out_dir, smoke=True,
             mesh=mesh, mesh_label="mini", shape_override=shape)
print("ROOFLINE_MINI_OK")
"""


def run(scale: float = 1.0) -> list[tuple[str, float, str]]:
    from . import roofline

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ)
    env["PYTHONPATH"] = (
        os.path.join(repo, "src") + os.pathsep + env.get("PYTHONPATH", "")
    )
    # a CPU dry-run: the child must never take the accelerator from its parent
    env["JAX_PLATFORMS"] = "cpu"
    with tempfile.TemporaryDirectory(prefix="roofline_mini_") as out_dir:
        proc = subprocess.run(
            [sys.executable, "-c", _MINI_SCRIPT, out_dir],
            capture_output=True, text=True, timeout=1800, env=env, cwd=repo,
        )
        if proc.returncode != 0 or "ROOFLINE_MINI_OK" not in proc.stdout:
            raise RuntimeError(
                f"mini dry-run failed (rc={proc.returncode}):\n{proc.stderr}"
            )
        rows_out: list[tuple[str, float, str]] = []
        for r in sorted(
            roofline.load_rows(out_dir, mesh="mini"),
            key=lambda r: (r.arch, r.shape),
        ):
            bound_us = r.step_seconds_lower_bound * 1e6
            ideal_us = r.model_flops_per_dev / roofline.PEAK_FLOPS * 1e6
            gap = bound_us / ideal_us if ideal_us > 0 else 0.0
            rows_out.append(
                (f"roofline/{r.arch}/{r.shape}/bound_us", bound_us,
                 f"dominant={r.dominant}")
            )
            rows_out.append(
                (f"roofline/{r.arch}/{r.shape}/gap", gap, "bound_over_ideal")
            )
        if not rows_out:
            raise RuntimeError("mini dry-run produced no roofline rows")
    return rows_out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description="Roofline rows from a mini 8-device dry-run (CI gate)."
    )
    ap.add_argument("--scale", type=float, default=1.0,
                    help="unused (deterministic bench); kept for harness symmetry")
    ap.add_argument("--json", default=None, metavar="PATH",
                    help="also write rows as JSON (BENCH_*.json perf trajectory)")
    args = ap.parse_args(argv)
    rows = run(scale=args.scale)
    print("name,us_per_call,derived")
    for name, value, derived in rows:
        print(f"{name},{value:.3f},{derived}")
    if args.json:
        payload = {
            "bench": "roofline",
            "scale": args.scale,
            "unix_time": time.time(),
            "python": platform.python_version(),
            "platform": platform.platform(),
            "rows": [
                {"name": name, "us_per_call": value, "derived": derived}
                for name, value, derived in rows
            ],
        }
        with open(args.json, "w") as f:
            json.dump(payload, f, indent=1)
        print(f"wrote {args.json}", file=sys.stderr)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
