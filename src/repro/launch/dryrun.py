import os
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=512")

"""Multi-pod dry-run (deliverable e): lower + compile every
(architecture × input-shape × mesh) cell against the production meshes and
record memory/cost/collective analysis for the roofline.

The two lines above MUST stay the first statements in this file: jax locks the
device count at first initialization, and the dry-run needs 512 placeholder
host devices so ``jax.make_mesh`` can build the 2×16×16 production mesh.  Do
NOT set this flag globally — smoke tests and benchmarks see 1 device.  It is
``setdefault``, not assignment, so a caller that already forced a smaller
topology (benchmarks/bench_roofline.py runs a mini 8-device dry-run) wins.

Usage:
    PYTHONPATH=src python -m repro.launch.dryrun --arch glm4-9b --shape train_4k
    PYTHONPATH=src python -m repro.launch.dryrun --all            # full matrix
    PYTHONPATH=src python -m repro.launch.dryrun --all --mesh multi

Artifacts: artifacts/dryrun/<arch>__<shape>__<mesh>.json with
memory_analysis, cost_analysis, per-opcode collective operand bytes, and
sharding metadata.  benchmarks/roofline.py consumes these.
"""

import argparse
import json
import sys
import time
import traceback
from typing import Any

import jax

from ..configs import ARCH_IDS, get_config, get_smoke_config
from ..models import model as M
from ..models.config import SHAPES, shape_applicable
from .hlo import collective_bytes, op_census
from .mesh import make_production_mesh
from .steps import build_step, rules_for

ARTIFACT_DIR = os.path.join("artifacts", "dryrun")


def _mem_analysis(compiled) -> dict[str, Any]:
    out: dict[str, Any] = {}
    try:
        mem = compiled.memory_analysis()
        for attr in (
            "argument_size_in_bytes",
            "output_size_in_bytes",
            "temp_size_in_bytes",
            "alias_size_in_bytes",
            "generated_code_size_in_bytes",
        ):
            if hasattr(mem, attr):
                out[attr] = int(getattr(mem, attr))
    except Exception as exc:  # noqa: BLE001 - backend may not implement
        out["error"] = str(exc)
    return out


def _cost_analysis(compiled) -> dict[str, float]:
    try:
        cost = compiled.cost_analysis()
        return {k: float(v) for k, v in cost.items() if isinstance(v, (int, float))}
    except Exception as exc:  # noqa: BLE001
        return {"error_msg": 0.0, "_error": str(exc)}  # type: ignore[dict-item]


def _sharded_nbytes(abstract_tree, shardings) -> int:
    """Per-device bytes of a sharded pytree (from NamedSharding shard shapes)."""
    import numpy as np

    total = 0
    for sds, sh in zip(jax.tree.leaves(abstract_tree), jax.tree.leaves(shardings)):
        shard_shape = sh.shard_shape(sds.shape)
        total += int(np.prod(shard_shape)) * sds.dtype.itemsize
    return total


def _compile_cell(cfg, shape, multi_pod, rules_overrides, step_kwargs=None, mesh=None):
    """Lower + compile; returns (compiled, built, mesh)."""
    from ..dist.sharding import ShardingRules  # noqa: F401 - typing aid

    if mesh is None:
        mesh = make_production_mesh(multi_pod=multi_pod)
    rules = rules_for(cfg, rules_overrides)
    built = build_step(cfg, mesh, rules, shape, **(step_kwargs or {}))
    with mesh:
        state_args = [built.abstract_state["params"]]
        if shape.kind == "train":
            state_args.append(built.abstract_state["opt_state"])
        lowered = built.fn.lower(*state_args, *built.abstract_inputs)
        compiled = lowered.compile()
    return compiled, built, mesh


def _cell_costs(compiled):
    cost = _cost_analysis(compiled)
    hlo = compiled.as_text()
    return cost, collective_bytes(hlo), hlo


def _scan_corrected(cfg, shape, multi_pod, rules_overrides, raw_cost, raw_coll,
                    step_kwargs=None, mesh=None):
    """Correct for XLA counting while(scan) bodies once, not × trip count.

    Compiles reduced-depth variants — one pattern period and zero layers
    (and, for enc-dec, a decoder-only variant) — and scales the per-period
    body delta by the scan trip count.  See EXPERIMENTS.md §Dry-run notes.
    """
    from ..models.model import _split_stack  # layer/period arithmetic

    n_scan, pattern, tail = _split_stack(cfg)
    p = len(pattern)
    variants = []  # (cfg_variant, multiplier applied to its body delta)
    if cfg.family == "encdec":
        c11 = cfg.replace(n_layers=p, n_enc_layers=1)
        c01 = cfg.replace(n_layers=p, n_enc_layers=0)
        c00 = cfg.replace(n_layers=0, n_enc_layers=0)
        cost11, coll11, _ = _cell_costs(_compile_cell(c11, shape, multi_pod, rules_overrides, step_kwargs, mesh)[0])
        cost01, coll01, _ = _cell_costs(_compile_cell(c01, shape, multi_pod, rules_overrides, step_kwargs, mesh)[0])
        cost00, coll00, _ = _cell_costs(_compile_cell(c00, shape, multi_pod, rules_overrides, step_kwargs, mesh)[0])
        deltas = [
            (_diff(cost11, cost01), _diff_coll(coll11, coll01), cfg.n_enc_layers - 1),
            (_diff(cost01, cost00), _diff_coll(coll01, coll00), n_scan - 1),
        ]
    else:
        c1 = cfg.replace(n_layers=p)
        c0 = cfg.replace(n_layers=0)
        cost1, coll1, _ = _cell_costs(_compile_cell(c1, shape, multi_pod, rules_overrides, step_kwargs, mesh)[0])
        cost0, coll0, _ = _cell_costs(_compile_cell(c0, shape, multi_pod, rules_overrides, step_kwargs, mesh)[0])
        deltas = [(_diff(cost1, cost0), _diff_coll(coll1, coll0), n_scan - 1)]

    corrected_cost = dict(raw_cost)
    corrected_coll = dict(raw_coll)
    bodies = []
    for dcost, dcoll, mult in deltas:
        bodies.append({"cost": dcost, "collectives": dcoll, "multiplier": mult})
        if mult <= 0:
            continue
        for key in ("flops", "transcendentals", "bytes accessed"):
            if key in corrected_cost and key in dcost:
                corrected_cost[key] = corrected_cost[key] + mult * dcost[key]
        for op, v in dcoll.items():
            corrected_coll[op] = corrected_coll.get(op, 0) + mult * v
    return corrected_cost, corrected_coll, bodies


def _diff(a: dict[str, float], b: dict[str, float]) -> dict[str, float]:
    return {k: a.get(k, 0.0) - b.get(k, 0.0) for k in set(a) | set(b) if not k.startswith("_")}


def _diff_coll(a: dict[str, int], b: dict[str, int]) -> dict[str, int]:
    return {k: a.get(k, 0) - b.get(k, 0) for k in set(a) | set(b)}


def run_cell(
    arch: str,
    shape_name: str,
    multi_pod: bool,
    *,
    out_dir: str = ARTIFACT_DIR,
    rules_overrides: dict[str, Any] | None = None,
    variant: str = "baseline",
    arch_overrides: dict[str, Any] | None = None,
    verbose: bool = True,
    scan_correction: bool = True,
    step_kwargs: dict[str, Any] | None = None,
    smoke: bool = False,
    mesh=None,
    mesh_label: str | None = None,
    shape_override=None,
) -> dict[str, Any]:
    """Lower+compile one cell; write and return the artifact record.

    ``smoke``/``mesh``/``mesh_label``/``shape_override`` support reduced-scale
    dry-runs (benchmarks/bench_roofline.py): the SMOKE_CONFIG instead of the
    published shape, an explicit mesh instead of the production one, and a
    custom ShapeConfig — the artifact's ``mesh`` field carries the label so
    roofline.load_rows can select the mini matrix.
    """
    cfg = get_smoke_config(arch) if smoke else get_config(arch)
    if arch_overrides:
        cfg = cfg.replace(**arch_overrides)
    shape = shape_override if shape_override is not None else SHAPES[shape_name]
    ok, why = shape_applicable(cfg, shape)
    mesh_name = mesh_label or ("multi" if multi_pod else "single")
    record: dict[str, Any] = {
        "arch": arch,
        "shape": shape.name,
        "mesh": mesh_name,
        "variant": variant,
        "status": "skipped",
    }
    if not ok:
        record["skip_reason"] = why
        _write(record, out_dir)
        if verbose:
            print(f"[dryrun] {arch} × {shape.name} × {mesh_name}: SKIP ({why})")
        return record

    t0 = time.monotonic()
    compiled, built, mesh = _compile_cell(
        cfg, shape, multi_pod, rules_overrides, step_kwargs, mesh
    )
    t_compile = time.monotonic() - t0

    mem = _mem_analysis(compiled)
    cost, coll, hlo = _cell_costs(compiled)
    census = op_census(hlo)

    if scan_correction:
        cost_corr, coll_corr, bodies = _scan_corrected(
            cfg, shape, multi_pod, rules_overrides, cost, coll, step_kwargs, mesh
        )
    else:
        cost_corr, coll_corr, bodies = cost, coll, []

    # analytic per-device state bytes from the shardings
    p_bytes = _sharded_nbytes(
        built.abstract_state["params"], built.in_shardings[0]
    )
    state_bytes = {"params_bytes_per_device": p_bytes}
    if shape.kind == "train":
        state_bytes["opt_bytes_per_device"] = _sharded_nbytes(
            built.abstract_state["opt_state"], built.in_shardings[1]
        )
    if shape.kind == "decode":
        state_bytes["cache_bytes_per_device"] = _sharded_nbytes(
            built.abstract_inputs[0], built.in_shardings[1]
        )

    total, active = M.param_counts(cfg)
    n_chips = mesh.devices.size
    record.update(
        {
            "status": "ok",
            "kind": shape.kind,
            "n_chips": n_chips,
            "seq_len": shape.seq_len,
            "global_batch": shape.global_batch,
            "tokens_per_step": shape.tokens_per_step,
            "params_total": total,
            "params_active": active,
            "memory_analysis": mem,
            "state_bytes": state_bytes,
            "cost_analysis_raw": cost,
            "cost_analysis": cost_corr,
            "collective_bytes_raw": coll,
            "collective_operand_bytes_per_device": coll_corr,
            "scan_bodies": bodies,
            "op_census": census,
            "compile_seconds": round(t_compile, 2),
            "sharding_preset": cfg.sharding,
            "accum_steps": int((step_kwargs or {}).get("accum_steps", 1)),
            "wall_seconds": round(time.monotonic() - t0, 2),
        }
    )
    _write(record, out_dir)
    if verbose:
        flops = cost_corr.get("flops", float("nan"))
        cbytes = sum(coll_corr.values())
        print(
            f"[dryrun] {arch} × {shape.name} × {mesh_name} [{variant}]: OK "
            f"flops/dev={flops:.3e} coll_bytes/dev={cbytes:.3e} "
            f"(compile {t_compile:.1f}s, total {record['wall_seconds']:.1f}s)"
        )
        print(f"  memory_analysis: {mem}")
    return record


def _write(record: dict[str, Any], out_dir: str) -> None:
    os.makedirs(out_dir, exist_ok=True)
    suffix = "" if record.get("variant", "baseline") == "baseline" else f"__{record['variant']}"
    name = f"{record['arch']}__{record['shape']}__{record['mesh']}{suffix}.json"
    with open(os.path.join(out_dir, name), "w") as f:
        json.dump(record, f, indent=1)


def optimized_settings(arch: str, shape_name: str):
    """The EXPERIMENTS.md §Perf knobs per cell kind (``--preset optimized``).

    Returns (arch_overrides, rules_overrides, step_kwargs).
    """
    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    arch_over: dict[str, Any] = {"embed_gather_constraint": True}
    rules_over: dict[str, Any] | None = None
    step_kwargs: dict[str, Any] | None = None
    if cfg.moe is not None:
        arch_over["moe_dispatch_mode"] = "tokens"
    if shape.kind == "train":
        arch_over.update({"loss_chunk": 512, "remat": "full"})
        step_kwargs = {"accum_steps": 8 if shape.global_batch % 8 == 0 else 1}
    if shape.kind == "decode" and cfg.n_kv_heads < 16:
        rules_over = {"kv_seq": "model"}
    return arch_over, rules_over, step_kwargs


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", choices=list(ARCH_IDS), default=None)
    ap.add_argument("--shape", choices=list(SHAPES), default=None)
    ap.add_argument("--mesh", choices=["single", "multi", "both"], default="both")
    ap.add_argument("--all", action="store_true", help="full matrix")
    ap.add_argument("--out", default=ARTIFACT_DIR)
    ap.add_argument("--skip-existing", action="store_true")
    ap.add_argument("--preset", choices=["baseline", "optimized"], default="baseline",
                    help="optimized = EXPERIMENTS.md §Perf knobs (variant 'opt')")
    args = ap.parse_args(argv)

    assert jax.device_count() >= 512, (
        f"dry-run needs 512 placeholder devices, got {jax.device_count()} — "
        "was jax initialized before the XLA_FLAGS line?"
    )

    archs = list(ARCH_IDS) if (args.all or args.arch is None) else [args.arch]
    shapes = list(SHAPES) if (args.all or args.shape is None) else [args.shape]
    meshes = ["single", "multi"] if args.mesh == "both" else [args.mesh]

    failures = []
    for arch in archs:
        for shape in shapes:
            for mesh_name in meshes:
                variant = "baseline" if args.preset == "baseline" else "opt"
                vsuffix = "" if variant == "baseline" else "__opt"
                suffix = f"{arch}__{shape}__{mesh_name}{vsuffix}.json"
                if args.skip_existing and os.path.exists(os.path.join(args.out, suffix)):
                    print(f"[dryrun] {suffix}: exists, skipping")
                    continue
                kwargs: dict[str, Any] = {}
                if args.preset == "optimized":
                    ao, ro, sk = optimized_settings(arch, shape)
                    kwargs = dict(arch_overrides=ao, rules_overrides=ro,
                                  step_kwargs=sk, variant="opt")
                try:
                    run_cell(arch, shape, mesh_name == "multi", out_dir=args.out, **kwargs)
                except Exception:  # noqa: BLE001
                    failures.append((arch, shape, mesh_name))
                    print(f"[dryrun] {arch} × {shape} × {mesh_name}: FAILED")
                    traceback.print_exc()
                finally:
                    jax.clear_caches()  # keep the long matrix run bounded in RAM
    if failures:
        print(f"[dryrun] {len(failures)} FAILURES: {failures}")
        return 1
    print("[dryrun] all requested cells passed")
    return 0


if __name__ == "__main__":
    sys.exit(main())
