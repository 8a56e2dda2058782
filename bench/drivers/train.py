"""Training cells: the program's ``run_training`` under a benchmark routine.

The traffic file gives the sequence length, the global batch, the pipeline
stages and microbatches, the learning-rate schedule, how many steps the check
follows and how many warm-up steps run before the window.

One ``run_training`` call builds the compiled step with its state, drives it
from the seed through its first steps, and then runs the window: a routine
the benchmark schedules in the session's EVOL bin, after the program's
``trainer::train_step``, reads each step's loss, the optimizer state after
the first step and the master weights after the last checked step, opens the
window at a step boundary once the warm-up steps are done, and ends the run
(``RunState.should_terminate``) at the first step boundary at or after
``--seconds``.  Begin and end routines in each loop bin write the bin as a
``bench/<BIN>`` span into the profiler's trace.

Once the program's state is freed, the float32 reference follows the checked
steps on the same data and initial weights, its layers split over the cell's
chips, and the check compares each step's loss, the per-leaf norm of the
first (clipped) gradient and the per-leaf norm of the master weights' change.
"""

from __future__ import annotations

import contextlib
import gc
import math
import statistics
import time

import numpy as np

#: leaves whose reference gradient norm is below this share of the median
#: leaf's move by round-off alone and are left out of the gradient and change
#: comparisons
NEGLIGIBLE_GRAD = 1e-3
LOOP_BINS = ("PRESTEP", "EVOL", "ANALYSIS", "CHECKPOINT", "OUTPUT")
#: the program's counters of the attention layers its step runs by each path
ATTENTION_COUNTERS = ("attn_flash_layers", "attn_chunked_layers")


def tokens_at(seed: int, step: int, batch: int, seq: int, vocab: int):
    """The traffic's tokens and next-token targets of one step: uniform ids
    from ``SeedSequence([seed, step])``, the last target of each row empty."""
    rng = np.random.default_rng(np.random.SeedSequence([seed, step]))
    tokens = rng.integers(0, vocab, (batch, seq), dtype=np.int32)
    targets = np.concatenate([tokens[:, 1:], np.full((batch, 1), -1, np.int32)], axis=1)
    return tokens, targets


def named_norms(F, tree) -> dict:
    """A tree of per-leaf norms in the program's layout, keyed by the
    reference's leaf names: the family ``F`` maps the one onto the other."""
    return {k: float(v) for k, v in F.reference_leaves(tree).items()}


def change_norms(F, R, arch, seed: int, master) -> dict:
    """Per reference leaf, the norm of the program's master weights' change
    from the initial weights that the reference ``R`` draws."""
    return {k: float(R.diff_norm(leaf, R.stacked_leaf(arch, seed, k)))
            for k, leaf in F.reference_leaves(master).items()}


def gap(program: float, reference: float, floor: float) -> float:
    return abs(program - reference) / max(abs(reference), floor)


def compare(prog: dict, ref: dict) -> list[dict]:
    """The checked numbers (without limits)."""
    losses = [gap(p, r, 0.0) for p, r in zip(prog["losses"], ref["losses"])]
    med_g = statistics.median(ref["grad_norms"].values())
    counted = [k for k, g in ref["grad_norms"].items() if g >= NEGLIGIBLE_GRAD * med_g]
    med_c = statistics.median(ref["change_norms"][k] for k in counted)
    return [
        {"name": "loss_gap", "value": max(losses) if losses else math.inf},
        {"name": "grad_gap", "value": max(
            gap(prog["grad_norms"][k], ref["grad_norms"][k], med_g) for k in counted)},
        {"name": "change_gap", "value": max(
            gap(prog["change_norms"][k], ref["change_norms"][k], med_c) for k in counted)},
        {"name": "fed_tokens_differ", "value": float(prog["fed_differ"])},
    ]


def run(ctx):
    import jax
    import jax.numpy as jnp

    from bench.harness import Outcome, family_module, memory_peak, reference_module
    from repro.core.clocks import counter_values
    from repro.core.params import reset_param_registry
    from repro.launch.train import TrainSettings, run_training
    from repro.timing import TimingSession

    cell, t, c = ctx.cell, ctx.cell.traffic, ctx.cell.config
    R, F = reference_module(cell), family_module(cell)
    stages = int(t.get("pipeline_stages", 0))
    n_layers = F.layers_for(c, t)
    cfg = F.program_cfg(c, n_layers)
    arch = F.ref_arch(c, n_layers)
    seed = ctx.key_seed
    batch, seq = int(t["global_batch"]), int(t["seq_len"])
    n_check, n_warm = int(t["check_steps"]), int(t["warmup_steps"])
    opt = R.AdamW(peak_lr=float(t["peak_lr"]), warmup_steps=max(min(100, t["lr_total_steps"] // 10), 1),
                  total_steps=int(t["lr_total_steps"]))
    settings = TrainSettings(
        arch=c["program_arch"], smoke=False, steps=10**9, global_batch=batch, seq_len=seq,
        ckpt_mode="off", ckpt_dir=None, report_every=0, seed=seed, data_mode="random",
        peak_lr=opt.peak_lr, lr_total_steps=opt.total_steps,
        pipeline_stages=stages, pipeline_model=stages > 0,
        pipeline_micro=int(t.get("micro_batches", 1)),
    )
    sess = TimingSession()
    sch = sess.scheduler
    st = {"losses": [], "fed_differ": 0, "window_losses": [], "steps": 0, "open": None}

    @jax.jit
    def grad_norms(m):
        # the optimizer's first moment after one step is (1 - b1) * g
        return jax.tree.map(lambda x: jnp.sqrt(jnp.sum(jnp.square(x))) / (1 - opt.b1), m)

    step_timer = "EVOL/trainer::train_step"

    def open_window(s):
        st["open"] = time.perf_counter()
        timer = sess.db.get(step_timer)
        st["span0"] = (timer.seconds(), timer.count)
        ctx.compiles.open = True
        if ctx.tracer is not None:
            ctx.tracer.start()

    def close_window(s, now):
        ctx.compiles.open = False
        st["close"] = now
        timer = sess.db.get(step_timer)
        st["span1"] = (timer.seconds(), timer.count)
        if ctx.tracer is not None and ctx.tracer.active:
            ctx.tracer.stop()
        s.should_terminate = True

    def after_step(s):
        i = s.iteration
        if i < n_check:
            st["losses"].append(float(s["metrics"]["loss"]))
            tokens, _ = tokens_at(seed, i, batch, seq, cfg.vocab_size)
            st["fed_differ"] += int(not np.array_equal(np.asarray(s["batch"]["tokens"]), tokens))
            if i == 0:
                st["grad_norms"] = named_norms(F, grad_norms(s["opt_state"]["m"]))
            if i == n_check - 1:
                st["change_norms"] = change_norms(F, R, arch, seed, s["opt_state"]["master"])
        if i == n_warm - 1:
            open_window(s)
            return
        if st["open"] is None:
            return
        st["steps"] += 1
        st["window_losses"].append(s["metrics"]["loss"])
        now = time.perf_counter()
        if ctx.tracer is not None and ctx.tracer.active and now - st["open"] >= t["trace_seconds"]:
            ctx.tracer.stop()
        if now - st["open"] >= ctx.seconds:
            close_window(s, now)

    sch.schedule(after_step, bin="EVOL", thorn="bench", after=("train_step",))

    # a bench/<BIN> span around each loop bin in the profiler's trace: the
    # begin routine is registered before the program's routines, the end
    # routine after them (from INITIAL, once the program has scheduled all)
    spans = {}

    def begin(bin_name):
        def fn(s):
            spans[bin_name] = jax.profiler.TraceAnnotation(f"bench/{bin_name}")
            spans[bin_name].__enter__()
        return fn

    def end(bin_name):
        def fn(s):
            spans.pop(bin_name).__exit__(None, None, None)
        return fn

    for b in LOOP_BINS:
        sch.schedule(begin(b), bin=b, thorn="bench", name=f"begin_{b}")

    def schedule_ends(s):
        for b in LOOP_BINS:
            sch.schedule(end(b), bin=b, thorn="bench", name=f"end_{b}",
                         after=tuple(r.name for r in sch.routines(b)
                                     if r.name != f"begin_{b}"))

    sch.schedule(schedule_ends, bin="INITIAL", thorn="bench")

    reset_param_registry()
    before = counter_values(ATTENTION_COUNTERS)
    try:
        with _planted(ctx.fault, sch, st):
            run_training(settings, cfg=cfg, session=sess)
    finally:
        # counted at start-up, so a run that fails later reports them too
        ctx.log("attention layers by path: " + ", ".join(
            f"{n} {a - b:g}" for n, a, b in
            zip(ATTENTION_COUNTERS, counter_values(ATTENTION_COUNTERS), before)))
    peak_bytes = memory_peak(ctx.devices)
    # the scheduler's routines hold the program's run state (parameters and
    # optimizer state): drop the session so they are gone before the reference
    del sess, sch
    gc.collect()

    window = st["close"] - st["open"]
    tokens_per_step = batch * seq
    losses = [float(x) for x in st["window_losses"]]
    failed = sum(1 for x in losses if not math.isfinite(x))
    trace = ctx.tracer.reduce(len(ctx.devices)) if ctx.tracer is not None else None

    # -- the reference, once the program's state is gone --------------------------
    t_ref = time.perf_counter()
    batches = [tokens_at(seed, i, batch, seq, cfg.vocab_size) for i in range(n_check)]
    ref = R.train_reference(arch, seed, batches, opt, devices=ctx.devices)
    checks = compare(st, ref)
    for ch in checks:
        ch["limit"] = float(cell.limits[ch["name"]])
    ctx.log(f"reference took {time.perf_counter() - t_ref:.1f} s; losses program "
            f"{st['losses']} reference {ref['losses']}")
    notes = {}
    if ctx.control:
        low = R.train_reference(arch, seed, batches, opt, precision="fp8", devices=ctx.devices)
        notes["control"] = {ch["name"]: ch["value"] for ch in compare({**low, "fed_differ": 0}, ref)}

    dims = F.dims(c, n_layers)
    span_s = st["span1"][0] - st["span0"][0]
    span_n = st["span1"][1] - st["span0"][1]
    return Outcome(
        end_to_end={
            "train_tokens_per_s": st["steps"] * tokens_per_step / window,
            "setup_s": st["open"] - ctx.t_start,
        },
        layer={
            "kind": "train",
            "window_s": window,
            "steps": st["steps"],
            "step_span_s": span_s,
            "step_span_n": span_n,
            "model_flops": st["steps"] * F.train_step_flops(dims, seq, batch),
            "chips": len(ctx.devices),
            "peak_flops": ctx.peak.bf16_flops,
            "trace": trace,
        },
        checks=checks,
        attempted=st["steps"],
        failed=failed,
        memory_peak_bytes=peak_bytes,
        trace=trace,
        compiles_in_window=ctx.compiles.count,
        notes=notes,
    )


#: the hand-off fault: this stage receives zeros in place of this microbatch's
#: activations, in every step
HANDOFF_STAGE, HANDOFF_MICRO = 1, 0


@contextlib.contextmanager
def _planted(fault: str | None, sch, st):
    """Break the timed path underneath the harness (tests and readings only)."""
    import jax
    import jax.numpy as jnp

    if fault is None:
        yield
    elif fault == "unchanged_state":
        def snap(s):
            st["snap"] = jax.tree.map(jnp.copy, (s["params"], s["opt_state"]))

        def restore(s):
            s["params"], s["opt_state"] = st.pop("snap")

        sch.schedule(snap, bin="EVOL", thorn="fault", before=("train_step",))
        sch.schedule(restore, bin="EVOL", thorn="fault", after=("train_step",),
                     before=("after_step",))
        yield
    elif fault == "half_batch":
        def drop_half(s):
            y = s["batch"]["targets"]
            if y.shape[0] > 1:
                s["batch"]["targets"] = y.at[y.shape[0] // 2:].set(-1)
            else:
                s["batch"]["targets"] = y.at[:, y.shape[1] // 2:].set(-1)

        sch.schedule(drop_half, bin="PRESTEP", thorn="fault", after=("fetch_data",))
        yield
    elif fault == "handoff":
        with _zeroed_handoff(HANDOFF_STAGE, HANDOFF_MICRO):
            yield
    else:
        raise ValueError(f"unknown fault {fault!r}")


@contextlib.contextmanager
def _zeroed_handoff(stage: int, micro: int):
    """While the 1F1B schedule is traced, wrap its forward ``ppermute`` (the
    ring ``d -> d + 1``) so that ``stage`` receives zeros in place of
    microbatch ``micro``: stage ``d`` receives at tick ``t + 1`` what stage
    ``d - 1`` sent at tick ``t``, microbatch ``t + 1 - d``.  (With two
    stages the backward ring is the same pairs, and is broken alike.)

    The tick loop is found by the program's own name for its body
    (``shard_body.<locals>.tick`` in ``repro.dist.pipeline``): if the program
    renames it, the fault finds no loop and raises."""
    import jax
    import jax.numpy as jnp

    real_loop, real_permute = jax.lax.fori_loop, jax.lax.ppermute
    tick: dict = {}
    hits = []

    def fori_loop(lower, upper, body, init, **kwargs):
        if not getattr(body, "__qualname__", "").endswith("shard_body.<locals>.tick"):
            return real_loop(lower, upper, body, init, **kwargs)

        def traced(t, c):
            tick["t"] = t
            try:
                return body(t, c)
            finally:
                tick.clear()

        return real_loop(lower, upper, traced, init, **kwargs)

    def ppermute(x, axis_name, perm):
        y = real_permute(x, axis_name, perm)
        forward = all(dst == (src + 1) % len(perm) for src, dst in perm)
        if "t" not in tick or not forward:
            return y
        d = jax.lax.axis_index(axis_name)
        hits.append(1)
        return jnp.where((d == stage) & (tick["t"] + 1 - d == micro), jnp.zeros_like(y), y)

    jax.lax.fori_loop, jax.lax.ppermute = fori_loop, ppermute
    try:
        yield
    finally:
        jax.lax.fori_loop, jax.lax.ppermute = real_loop, real_permute
    if not hits:
        raise RuntimeError("the hand-off fault found no 1F1B tick loop to break")
