#!/usr/bin/env python3
"""Run the trainer, the server and the Pallas kernels once on a TPU.

    python chip_smoke.py                # one chip: train, serve, kernels
    python chip_smoke.py --four-chips   # four chips: the multi-chip training paths

The model is llama3.2-1b at its published widths, cut to 4 layers, with random
weights and data made from ``--seed``.

One chip, through the entry points a user calls:

* train: ``run_training`` at seq 2048, global batch 2, 6 steps, saving a
  checkpoint under ``.chip_smoke/``.  Every loss is finite and the first
  cross-entropy is near ln(vocab), as it must be for random weights.
* serve: ``run_serving`` with 8 requests of 512 prompt tokens and 32 new
  tokens on 8 slots.  Every request completes with its tokens, and a prompt's
  prefill logits agree with a full ``forward`` over it.
* kernels: flash attention (forward and vjp) and fused RMSNorm at the model's
  widths, lowered through Mosaic and compared with their ``ref.py``.

Four chips (``--four-chips``, only this phase): the 1F1B transformer pipeline
over 4 stages and the (data, model) = (2, 2) mesh, each compared with the
one-chip run of the same config, seed and data.

The last line of stdout is ``{"ok": true, "device": {...}}``.  Any failure
exits nonzero without it, and so does a host where JAX finds no TPU.
"""

from __future__ import annotations

import argparse
import gc
import json
import math
import os
import shutil
import statistics
import sys

REPO = os.path.dirname(os.path.abspath(__file__))
WORKDIR = os.path.join(REPO, ".chip_smoke")
ARCH = "llama3.2-1b"
N_LAYERS = 4
SEQ_LEN = 2048
GLOBAL_BATCH = 2

#: bf16 keeps 8 significant bits: one unit in the last place is 2**-8 of a
#: value's magnitude.  Tolerances below are stated in these units.
BF16_ULP = 2.0 ** -8


def emit(**row) -> None:
    print(json.dumps(row, default=float), flush=True)


def check(ok: bool, what: str) -> None:
    if not ok:
        raise AssertionError(what)


def peak_bytes_in_use() -> int:
    import jax

    return jax.devices()[0].memory_stats()["peak_bytes_in_use"]


def _step_rows(log_path: str) -> list[dict]:
    """Per-step metrics and cumulative train-step seconds from the run's log."""
    from repro.core import TimerLogger

    rows = []
    for rec in TimerLogger(log_path).read_all():
        step = rec["timers"]["EVOL/trainer::train_step"]
        rows.append({**rec["extra"], "step_seconds_total": step["walltime"]})
    return rows


def train(cfg, *, seed: int, steps: int, name: str, ckpt: bool, **settings) -> dict:
    """One ``run_training`` call; returns its summary plus per-step rows."""
    from repro.core.params import reset_param_registry
    from repro.launch.train import TrainSettings, run_training
    from repro.timing import TimingSession

    reset_param_registry()
    run_dir = os.path.join(WORKDIR, name)
    shutil.rmtree(run_dir, ignore_errors=True)
    os.makedirs(run_dir)
    sess = TimingSession()
    summary = run_training(
        TrainSettings(
            arch=cfg.name, smoke=False, steps=steps, seq_len=SEQ_LEN,
            global_batch=GLOBAL_BATCH, seed=seed, report_every=0,
            ckpt_dir=os.path.join(run_dir, "ckpt") if ckpt else None,
            ckpt_mode="fixed" if ckpt else "off", ckpt_keep_n=1, restore=False,
            log_path=os.path.join(run_dir, "train.jsonl"),
            **settings,
        ),
        cfg=cfg,
        session=sess,
    )
    summary["rows"] = _step_rows(os.path.join(run_dir, "train.jsonl"))
    summary["compile_s"] = (
        sess.db.get("STARTUP/compile").seconds() if sess.db.exists("STARTUP/compile") else None
    )
    if ckpt:
        from repro.checkpoint import CheckpointManager

        manager = CheckpointManager(os.path.join(run_dir, "ckpt"))
        summary["checkpoints"] = [step for step, _ in manager.checkpoints()]
        summary["ckpt_write_s"] = sess.db.get("CHECKPOINT/adaptcheck::write").seconds()
    return summary


def train_phase(cfg, seed: int) -> None:
    steps = 6
    out = train(cfg, seed=seed, steps=steps, name="train", ckpt=True)
    rows = out["rows"]
    check(len(rows) == steps, f"logged {len(rows)} of {steps} steps")
    losses = [r["loss"] for r in rows]
    check(all(math.isfinite(x) for x in losses), f"non-finite loss in {losses}")
    # random weights: logits have std ~0.02 * sqrt(d_model) ~ 0.9, which puts
    # the first cross-entropy ~0.4 above the uniform ln(vocab)
    ln_v = math.log(cfg.vocab_size)
    check(abs(rows[0]["ce"] - ln_v) < 1.0, f"first ce {rows[0]['ce']} not near ln V {ln_v}")
    check(out["checkpoints"] == [steps], f"checkpoints on disk: {out['checkpoints']}")
    totals = [0.0] + [r["step_seconds_total"] for r in rows]
    step_s = [b - a for a, b in zip(totals, totals[1:])]
    median = statistics.median(step_s[1:])  # the first step is the warm-up
    emit(
        phase="train", arch=cfg.name, n_layers=cfg.n_layers, seq_len=SEQ_LEN,
        global_batch=GLOBAL_BATCH, compile_s=out["compile_s"], step_s=step_s,
        median_step_s_after_warmup=median,
        tokens_per_s=GLOBAL_BATCH * SEQ_LEN / median,
        peak_bytes_in_use=peak_bytes_in_use(),
        losses=losses, first_ce=rows[0]["ce"], ln_vocab=ln_v,
        ckpt_write_s=out["ckpt_write_s"],
    )
    shutil.rmtree(os.path.join(WORKDIR, "train"), ignore_errors=True)


def serve_phase(cfg, seed: int) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core.params import reset_param_registry
    from repro.launch.serve import run_serving
    from repro.models import model as M
    from repro.serving import Request
    from repro.timing import TimingSession

    n_requests, prompt_len, max_new = 8, 512, 32
    reset_param_registry()
    sess = TimingSession()
    with sess:
        engine = run_serving(
            cfg.name, n_requests, prompt_len, max_new, n_slots=8, seed=seed,
            session=sess, cfg=cfg,
        )
        done = engine.completed
        check(len(done) == n_requests, f"{len(done)} of {n_requests} requests completed")
        check(all(r.status == "completed" and len(r.tokens) == max_new for r in done),
              "a request finished without its tokens")
        # one more prompt through the same engine, against a full forward
        prompt = np.random.default_rng(seed + 1).integers(0, cfg.vocab_size, prompt_len)
        handle = engine.submit(Request(n_requests, prompt=list(prompt), max_new_tokens=1))
        engine.run_until_idle()
        first_token = handle.result().tokens[0]
    tokens = jnp.asarray(prompt[None], jnp.int32)
    ref = jax.jit(lambda p, t: M.forward(cfg, p, {"tokens": t})[0][0, -1])(engine.params, tokens)
    _, pre = jax.jit(lambda p, t, c: M.prefill(cfg, p, {"tokens": t}, c))(
        engine.params, tokens, M.init_cache(cfg, 1, engine.max_seq)
    )
    ref = np.asarray(ref, np.float32)[: cfg.vocab_size]
    pre = np.asarray(pre[0], np.float32)[: cfg.vocab_size]
    err = float(np.max(np.abs(pre - ref)))
    # prefill and forward run the same bf16 layers over the same prompt and
    # differ only in how XLA fuses and orders reductions; allow 4 bf16 ulps
    # at the largest logit
    tol = 4 * BF16_ULP * float(np.max(np.abs(ref)))
    check(err <= tol, f"prefill vs forward logits differ by {err} > {tol}")
    check(ref[first_token] >= ref.max() - tol,
          f"served token {first_token} is not a top logit of the reference")
    stats = engine.stats()
    emit(
        phase="serve", requests=n_requests, prompt_len=prompt_len, max_new=max_new,
        completed=stats["completed"], tokens=stats["tokens"],
        throughput_tokens_per_s=stats["throughput_tokens_per_s"],
        p95_ttft_s=stats["p95_ttft_s"], prefill_vs_forward_max_err=err, tolerance=tol,
    )


def _mosaic(fn, *args) -> None:
    import jax

    text = jax.jit(fn).lower(*args).compile().as_text()
    check("tpu_custom_call" in text, f"{getattr(fn, '__name__', fn)} did not lower through Mosaic")


def _close(name: str, got, want, rel: float) -> float:
    """Max abs error of ``got`` against ``want``, checked against ``rel``
    times the reference's largest magnitude."""
    import numpy as np

    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    err = float(np.max(np.abs(got - want)))
    bound = rel * float(np.max(np.abs(want)))
    check(err <= bound, f"{name}: max error {err} > {bound}")
    return err


def kernel_phase(cfg, seed: int) -> None:
    import jax
    import jax.numpy as jnp

    from repro.kernels.flash_attention.ops import flash_attention
    from repro.kernels.flash_attention.ref import attention_ref
    from repro.kernels.rmsnorm.ops import rms_norm_fused
    from repro.kernels.rmsnorm.ref import rms_norm_ref

    b, s, h, kv, hd = GLOBAL_BATCH, SEQ_LEN, cfg.n_heads, cfg.n_kv_heads, cfg.resolved_head_dim
    ks = jax.random.split(jax.random.PRNGKey(seed), 7)
    bf16, f32 = jnp.bfloat16, jnp.float32
    q = jax.random.normal(ks[0], (b, s, h, hd), f32).astype(bf16)
    k = jax.random.normal(ks[1], (b, s, kv, hd), f32).astype(bf16)
    v = jax.random.normal(ks[2], (b, s, kv, hd), f32).astype(bf16)
    do = jax.random.normal(ks[3], (b, s, h, hd), f32).astype(bf16)
    x = jax.random.normal(ks[4], (b, s, cfg.d_model), f32).astype(bf16)
    w = (1.0 + 0.1 * jax.random.normal(ks[5], (cfg.d_model,), f32)).astype(bf16)
    dy = jax.random.normal(ks[6], x.shape, f32).astype(bf16)

    def flash(q, k, v):
        return flash_attention(q, k, v, causal=True)

    def flash_vjp(q, k, v, do):
        return jax.vjp(flash, q, k, v)[1](do)

    def norm(x, w):
        return rms_norm_fused(x, w, cfg.norm_eps)

    def norm_vjp(x, w, dy):
        return jax.vjp(norm, x, w)[1](dy)

    for fn, args in ((flash, (q, k, v)), (flash_vjp, (q, k, v, do)),
                     (norm, (x, w)), (norm_vjp, (x, w, dy))):
        _mosaic(fn, *args)

    # references in f32 from the same bf16 inputs, at full matmul precision
    def attn_ref(q, k, v):
        return attention_ref(q.astype(f32), k.astype(f32), v.astype(f32), causal=True)

    def norm_ref(x, w):
        return rms_norm_ref(x.astype(f32), w.astype(f32), cfg.norm_eps)

    with jax.default_matmul_precision("highest"):
        want_o = jax.jit(attn_ref)(q, k, v)
        want_g = jax.jit(lambda *a: jax.vjp(attn_ref, *a[:3])[1](a[3].astype(f32)))(q, k, v, do)
        want_y = jax.jit(norm_ref)(x, w)
        want_n = jax.jit(lambda x, w, dy: jax.vjp(norm_ref, x, w)[1](dy.astype(f32)))(x, w, dy)
    got_o = jax.jit(flash)(q, k, v)
    got_g = jax.jit(flash_vjp)(q, k, v, do)
    got_y = jax.jit(norm)(x, w)
    got_n = jax.jit(norm_vjp)(x, w, dy)
    # bf16 outputs: the kernels' own CPU-test tolerance, 2e-2, of each
    # result's scale (about 5 bf16 ulps)
    errs = {"flash_out": _close("flash out", got_o, want_o, 2e-2)}
    for name, g, r in zip(("dq", "dk", "dv"), got_g, want_g):
        errs[f"flash_{name}"] = _close(f"flash {name}", g, r, 2e-2)
    errs["rmsnorm_out"] = _close("rmsnorm out", got_y, want_y, 2e-2)
    for name, g, r in zip(("dx", "dw"), got_n, want_n):
        errs[f"rmsnorm_{name}"] = _close(f"rmsnorm {name}", g, r, 2e-2)
    emit(phase="kernels", shapes={"q": q.shape, "k": k.shape, "x": x.shape},
         lowered="tpu_custom_call", max_abs_err=errs)


def four_chip_phase(cfg, seed: int) -> None:
    steps = 3
    runs = {
        "one_chip": dict(),
        "pipeline_1f1b_4_stages": dict(
            pipeline_stages=4, pipeline_model=True, pipeline_micro=GLOBAL_BATCH,
        ),
        "mesh_data2_model2": dict(mesh_shape=(2, 2)),
    }
    results = {}
    for name, settings in runs.items():
        out = train(cfg, seed=seed, steps=steps, name=name, ckpt=False, **settings)
        results[name] = out
        emit(phase="four_chips", run=name, losses=[r["loss"] for r in out["rows"]],
             state_bytes_by_device=out["state_bytes_by_device"])
        gc.collect()
    ref = [r["loss"] for r in results["one_chip"]["rows"]]
    ref_bytes = sum(results["one_chip"]["state_bytes_by_device"].values())
    for name in list(runs)[1:]:
        got = [r["loss"] for r in results[name]["rows"]]
        check(all(math.isfinite(x) for x in got), f"{name}: non-finite loss in {got}")
        # step 0 sees the same weights and data: the losses differ only by
        # bf16 reduction order, within one bf16 ulp of the loss
        check(abs(got[0] - ref[0]) <= BF16_ULP * abs(ref[0]),
              f"{name}: first loss {got[0]} vs one chip {ref[0]}")
        # later steps follow AdamW updates whose sign can flip on near-zero
        # gradients, so allow a 2% band around the one-chip trajectory
        for i in range(1, steps):
            check(abs(got[i] - ref[i]) <= 0.02 * abs(ref[i]),
                  f"{name}: step {i} loss {got[i]} outside 2% of {ref[i]}")
        held = results[name]["state_bytes_by_device"]
        check(len(held) == 4, f"{name}: state on devices {sorted(held)}, not all 4")
        check(max(held.values()) < ref_bytes,
              f"{name}: one device holds the whole state ({max(held.values())} bytes)")
    emit(phase="four_chips", compared_with="one_chip", first_loss_rel_tol=BF16_ULP,
         later_loss_rel_band=0.02)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the multi-chip training paths (needs 4 chips)")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise SystemExit(f"chip_smoke needs a TPU; JAX found {devices[0].platform}")
    need = 4 if args.four_chips else 1
    if len(devices) < need:
        raise SystemExit(f"chip_smoke needs {need} chips; JAX found {len(devices)}")

    sys.path.insert(0, os.path.join(REPO, "src"))
    from repro.configs import get_config
    from repro.launch.cache import configure_compile_cache

    emit(phase="setup", compile_cache=configure_compile_cache(),
         devices=[d.device_kind for d in devices])
    cfg = get_config(ARCH).replace(n_layers=N_LAYERS)
    if args.four_chips:
        four_chip_phase(cfg, args.seed)
    else:
        train_phase(cfg, args.seed)
        gc.collect()
        serve_phase(cfg, args.seed)
        gc.collect()
        kernel_phase(cfg, args.seed)
    shutil.rmtree(WORKDIR, ignore_errors=True)
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind, "count": len(devices),
    }}))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
