#!/usr/bin/env python3
"""Cases of the four-chip pipeline cell on four virtual CPU devices.

    python3 bench/tests/four_devices.py <case> [<case> ...]

The device count is fixed when JAX starts, so these run in a process of their
own (``test_pipeline_cell.py`` starts it); each case prints one JSON line.

* ``split``: the float32 reference with its layers split over the four
  devices, and over one, against the unsplit reference (the whole model's
  loss differentiated at once and stepped by AdamW), three steps at the tiny
  size; and the same for the fp8 control;
* ``run:<fault>``: a run of the ``train.glm4-9b.pp4`` cell at the tiny size,
  with the harness's look for a chip skipped, ``<fault>`` planted underneath
  (``none`` for none) and, with no fault, the control's readings too;
* ``readings:<first seed>:<seeds>``: the sound runs' and the control's
  readings at the tiny size, from which ``conftest.TINY_LIMITS`` was set.
"""

import json
import math
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=4").strip()
os.environ["JAX_PLATFORMS"] = "cpu"
for p in (HERE, ROOT, os.path.join(ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

CELL = "train.glm4-9b.pp4"
SEED = 2**31 + 41


def tiny_pp4():
    from conftest import tiny_cell

    return tiny_cell(CELL, seq_len=32, global_batch=8, micro_batches=8, warmup_steps=4,
                     check_steps=3)


def unsplit_reference(a, seed, batches, opt, precision="f32"):
    """The reference's training, every leaf whole on one device."""
    import jax
    import jax.numpy as jnp

    from bench.reference import dense_gqa as R

    with jax.default_matmul_precision("highest"):
        grad_fn = jax.jit(jax.value_and_grad(
            lambda w, t, y: R.loss_fn(a, R._rounded(w), t, y, precision)))
        master = R.stacked_weights(a, seed)
        m = {k: jnp.zeros_like(x) for k, x in master.items()}
        v = {k: jnp.zeros_like(x) for k, x in master.items()}
        losses, first = [], None
        for i, (tokens, targets) in enumerate(batches):
            loss, g = grad_fn(master, jnp.asarray(tokens), jnp.asarray(targets))
            losses.append(float(loss))
            sq = {k: float(jnp.sum(jnp.square(x))) for k, x in g.items()}
            scale = R.clip_scale(opt, math.sqrt(sum(sq.values())))
            if first is None:
                first = {k: scale * math.sqrt(x) for k, x in sq.items()}
            master, m, v = R.adamw_update(opt, master, m, v, g, scale, i + 1, R.lr_at(opt, i))
        change = {k: float(R.diff_norm(x, R.stacked_leaf(a, seed, k))) for k, x in master.items()}
    return {"losses": losses, "grad_norms": first, "change_norms": change}


def rel_gap(x: dict, y: dict) -> float:
    """The widest relative difference of two references' numbers."""
    pairs = list(zip(x["losses"], y["losses"]))
    pairs += [(x[k][n], y[k][n]) for k in ("grad_norms", "change_norms") for n in y[k]]
    return max(abs(p - q) / max(abs(q), 1e-30) for p, q in pairs)


def case_split() -> dict:
    import jax

    from bench.drivers.train import tokens_at
    from bench.harness import family_module
    from bench.reference import dense_gqa as R

    cell = tiny_pp4()
    F = family_module(cell)
    t = cell.traffic
    arch = F.ref_arch(cell.config, F.layers_for(cell.config, t))
    batches = [tokens_at(SEED, i, t["global_batch"], t["seq_len"], arch.vocab) for i in range(3)]
    opt = R.AdamW(peak_lr=t["peak_lr"], warmup_steps=100, total_steps=t["lr_total_steps"])
    out = {"layers": arch.n_layers}
    for precision in ("f32", "fp8"):
        whole = unsplit_reference(arch, SEED, batches, opt, precision)
        for n in (4, 1):
            split = R.train_reference(arch, SEED, batches, opt, precision,
                                      devices=jax.devices()[:n])
            out[f"gap_{n}" + ("" if precision == "f32" else "_fp8")] = rel_gap(split, whole)
    return out


def run(seed: int, fault: str | None, control: bool) -> dict:
    from bench import harness
    from bench.peaks import PEAKS

    cell = tiny_pp4()
    outs: list = []
    r = harness.run_cell(cell.name, seed, 0.5, False, require_chip=False, cell=cell,
                         peak=PEAKS["TPU v5 lite"], fault=fault, control=control, outcome=outs)
    low = outs[0].notes.get("control")
    control_correct = None
    if low is not None:
        # the control put in the program's place, held to the cell's limits
        # by the harness's own rule, as bench/readings.py holds it
        control_correct = harness.Outcome(
            end_to_end={}, layer={}, attempted=0, failed=0, memory_peak_bytes=0,
            checks=[{"name": k, "value": v, "limit": r["checks"][k]["limit"]}
                    for k, v in low.items()],
        ).correct
    return {
        "seed": seed, "fault": fault, "correct": r["correct"], "chips": r["device"]["count"],
        "checks": {k: c["value"] for k, c in r["checks"].items()},
        "limits": {k: c["limit"] for k, c in r["checks"].items()},
        "control": low, "control_correct": control_correct,
    }


def main(cases: list[str]) -> int:
    for case in cases:
        if case == "split":
            out = case_split()
        elif case.startswith("run:"):
            fault = case.split(":", 1)[1]
            out = run(SEED, None if fault == "none" else fault, control=fault == "none")
        elif case.startswith("readings:"):
            _, first, n = case.split(":")
            for i in range(int(n)):
                print(json.dumps(run(int(first) + i, None, control=i < 3)), flush=True)
            continue
        else:
            raise SystemExit(f"unknown case {case!r}")
        print(json.dumps({"case": case, **out}), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main(sys.argv[1:]))
