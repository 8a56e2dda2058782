"""Serving cells: the program's ``ServeSession`` under an open-loop generator.

The traffic file (read by ``bench/traffic.py``) gives the arrival rate, the
prompt and output lengths, the engine's slots and cache length, and how many
finished requests the check compares.  Weights are made on the device in one
jitted call from the seed (the reference's ``per_layer`` rule, bf16), the
engine is warmed on every prompt bucket of the traffic, and arrivals start
``lead_s`` seconds before the window so it opens at steady occupancy.  Every
request whose scheduled arrival falls in the window counts, timed from that
scheduled arrival; arrivals continue after the window until every counted
request has finished.

The driver keeps its own account of the engine (first-in first-out
admission, one token per active row per decode step) to know each decode
step's rows and positions; it checks that account against every finished
request and leaves the step counts out where they disagree.

Once the window's requests are done and the engine is freed, the float32
reference runs over a sample of them drawn from the seed, the request with
the most served tokens among them, and reads how far each served token's
logit lies below its best.
"""

from __future__ import annotations

import gc
import time
from collections import deque
from dataclasses import dataclass

import numpy as np


@dataclass
class Tracked:
    rid: int
    at: float            # scheduled arrival, time.monotonic
    prompt: list[int]
    max_new: int
    generated: int = 0
    submitted: float = 0.0
    result: object = None


def build(ctx):
    """(engine, session, cfg, arch, dims) with weights from the seed."""
    import jax
    import jax.numpy as jnp

    from bench.harness import family_module, reference_module
    from repro.core.params import reset_param_registry
    from repro.serving import ServeSession
    from repro.serving.slo import ServiceLevel
    from repro.timing import TimingSession

    cell, t, c = ctx.cell, ctx.cell.traffic, ctx.cell.config
    R, F = reference_module(cell), family_module(cell)
    n_layers = F.layers_for(c, t)
    cfg = F.program_cfg(c, n_layers)
    arch = F.ref_arch(c, n_layers)
    params = F.program_tree(R.make_weights(arch, ctx.key_seed, jnp.bfloat16))
    jax.block_until_ready(params)
    sess = TimingSession()
    reset_param_registry()
    engine = ServeSession(
        cfg, params, session=sess, n_slots=int(t["n_slots"]), max_seq=int(t["max_seq"]),
        block_size=int(t["block_size"]), slo=ServiceLevel(), control=True,
    )
    return engine, sess, cfg, arch, F.dims(c, n_layers)


def warm(engine, t: dict, vocab: int, seed: int) -> None:
    """Every shape the traffic uses: one prefill per prompt bucket, the splice,
    the decode step, then all buckets admitted together."""
    from repro.serving import Request

    rng = np.random.default_rng([seed, 2])
    rid = -1
    for together in (False, True):
        for b in sorted(int(x) for x in t["prompt"]["buckets"]):
            engine.submit(Request(rid, [int(x) for x in rng.integers(0, vocab, b)],
                                  max_new_tokens=3))
            rid -= 1
            if not together:
                engine.run_until_idle()
        engine.run_until_idle()


def drive(ctx, engine, sess, arrivals, t: dict, dims) -> dict:
    """Run the open loop; returns the window's raw record."""
    import jax

    from bench.harness import family_module
    from repro.serving import Request

    F = family_module(ctx.cell)
    lead, seconds = float(t["lead_s"]), float(ctx.seconds)
    origin = time.monotonic() + 0.01
    open_at, close_at = origin + lead, origin + lead + seconds
    trace_at = close_at - float(t["trace_seconds"])
    pending = deque(Tracked(a.rid, origin + a.at, a.prompt, a.max_new) for a in arrivals)
    queued: deque[Tracked] = deque()
    active: dict[int, Tracked] = {}
    done: list[Tracked] = []
    prefills: list[tuple[float, int]] = []
    decodes: list[tuple[float, list[int]]] = []
    mismatch = 0
    snap: dict[str, tuple] = {}
    state = "lead"

    def spans():
        p, d = sess.db.get("serve/prefill"), sess.db.get("serve/decode")
        return (p.seconds(), p.count, d.seconds(), d.count)

    while True:
        now = time.monotonic()
        if state == "lead" and now >= open_at:
            snap["open"] = (now, spans())
            ctx.compiles.open = True
            state = "window"
        if state == "window":
            # the window's last seconds: stopping the profiler holds this loop
            # for seconds, and at the close that stall falls outside the window
            if ctx.tracer is not None and not ctx.tracer.active and now >= trace_at:
                ctx.tracer.start()
            if now >= close_at:
                ctx.compiles.open = False
                if ctx.tracer is not None and ctx.tracer.active:
                    ctx.tracer.stop()
                snap["close"] = (now, spans())
                state = "drain"
        if pending and pending[0].at <= now:
            with jax.profiler.TraceAnnotation("bench/submit"):
                while pending and pending[0].at <= now:
                    r = pending.popleft()
                    r.submitted = time.monotonic()
                    # the handle is not kept: it holds the engine, and with it the
                    # weights, which must be gone before the reference runs
                    engine.submit(Request(r.rid, list(r.prompt), max_new_tokens=r.max_new))
                    queued.append(r)
        if state == "drain":
            counted_left = any(open_at <= r.at < close_at for r in list(queued) + list(active.values()))
            if not counted_left or now > close_at + float(t["drain_timeout_s"]):
                break
        if engine.queue_depth == 0 and engine.active_slots == 0:
            wait = pending[0].at - now if pending else 0.001
            with jax.profiler.TraceAnnotation("bench/wait_arrival"):
                time.sleep(min(max(wait, 0.0), 0.002))
            continue
        qd0, shed0 = engine.queue_depth, len(engine.shed_results)
        tb = time.monotonic()
        with jax.profiler.TraceAnnotation("bench/engine_step"):
            finished = engine.step()
        admitted = qd0 - engine.queue_depth - (len(engine.shed_results) - shed0)
        for _ in range(admitted):
            r = queued.popleft()
            r.generated = 1
            active[r.rid] = r
            prefills.append((tb, len(r.prompt)))
        # a one-token request finishes at its prefill and never decodes
        rows = [r for r in active.values() if r.max_new > 1]
        if rows:
            decodes.append((tb, [len(r.prompt) + r.generated for r in rows]))
            for r in rows:
                r.generated += 1
        for f in finished:
            r = active.pop(f.rid, None)
            if r is None:
                mismatch += 1
                continue
            r.result = f
            mismatch += int(r.generated != len(f.tokens))
            done.append(r)
    # anything still queued or active at the deadline never came
    never = list(queued) + list(active.values())
    t_open, s0 = snap["open"]
    t_close, s1 = snap["close"]
    in_window = lambda x: t_open <= x < t_close  # noqa: E731
    win_prefills = [p for tb, p in prefills if in_window(tb)]
    win_decodes = [a for tb, a in decodes if in_window(tb)]
    decode_flops = decode_ideal = 0.0
    for attended in win_decodes:
        f, b = F.decode_step_cost(dims, attended)
        decode_flops += f
        decode_ideal += max(f / ctx.peak.bf16_flops, b / ctx.peak.hbm_bytes)
    return {
        "open_at": open_at, "close_at": close_at, "t_open": t_open, "t_close": t_close,
        "done": done, "never": never, "mismatch": mismatch,
        "prefill_span_s": s1[0] - s0[0], "prefill_span_n": s1[1] - s0[1],
        "decode_span_s": s1[2] - s0[2], "decode_span_n": s1[3] - s0[3],
        "prefill_tokens": sum(win_prefills),
        "prefill_flops": sum(F.prefill_flops(dims, p) for p in win_prefills),
        "decode_steps": len(win_decodes), "decode_flops": decode_flops,
        "decode_ideal_s": decode_ideal,
    }


def summarize(rec: dict) -> dict:
    """End-to-end numbers over the requests that arrived in the window."""
    open_at, close_at = rec["open_at"], rec["close_at"]
    counted = [r for r in rec["done"] if open_at <= r.at < close_at]
    never = [r for r in rec["never"] if open_at <= r.at < close_at]
    ttft = [r.result.first_token_at - r.at for r in counted]
    tpot = [(r.result.finished_at - r.result.first_token_at) / (len(r.result.tokens) - 1)
            for r in counted if len(r.result.tokens) > 1]
    queue = [r.result.admitted_at - r.at for r in counted]
    lag = [r.submitted - r.at for r in counted]
    p95 = lambda xs: float(np.percentile(xs, 95)) if xs else None  # noqa: E731
    return {
        "counted": counted, "never": never,
        "ttft_p95_ms": 1e3 * p95(ttft) if ttft else None,
        "tpot_p95_ms": 1e3 * p95(tpot) if tpot else None,
        "queue_p95_ms": 1e3 * p95(queue) if queue else None,
        "lag_p95_ms": 1e3 * p95(lag) if lag else None,
        "lag_max_ms": 1e3 * max(lag) if lag else None,
    }


def check_sample(counted: list, k: int, seed: int) -> list:
    """The request with the most served tokens and ``k - 1`` others drawn from
    the seed."""
    if not counted:
        return []
    longest = max(counted, key=lambda r: (len(r.result.tokens), -r.rid))
    rest = [r for r in counted if r is not longest]
    rng = np.random.default_rng([seed, 1])
    pick = rng.choice(len(rest), size=min(k - 1, len(rest)), replace=False) if rest else []
    return [longest] + [rest[i] for i in sorted(pick)]


def run(ctx):
    from bench.harness import Outcome, memory_peak, reference_module
    from bench.traffic import serve_arrivals

    t = ctx.cell.traffic
    R = reference_module(ctx.cell)
    engine, sess, cfg, arch, dims = build(ctx)
    if ctx.fault:
        _plant(ctx.fault, engine)
    warm(engine, t, cfg.vocab_size, ctx.seed)
    setup_s = time.perf_counter() - ctx.t_start
    segments = [float(t["lead_s"]), ctx.seconds, float(t["tail_arrivals_s"])]
    arrivals = serve_arrivals(t, ctx.seed, segments, cfg.vocab_size)
    rec = drive(ctx, engine, sess, arrivals, t, dims)
    peak_bytes = memory_peak(ctx.devices)
    s = summarize(rec)
    ctx.log(f"window {rec['t_close'] - rec['t_open']:.3f} s, {len(s['counted'])} requests "
            f"done, {len(s['never'])} never finished, generator lag p95 {s['lag_p95_ms']} ms "
            f"max {s['lag_max_ms']} ms, account mismatches {rec['mismatch']}")
    trace = ctx.tracer.reduce(len(ctx.devices)) if ctx.tracer is not None else None
    sample = check_sample(s["counted"], int(t["check_requests"]), ctx.seed)
    seqs = [(r.prompt, list(r.result.tokens)) for r in sample]
    del engine, sess
    gc.collect()

    t_ref = time.perf_counter()
    gaps = R.served_token_gaps(arch, ctx.key_seed, seqs, pad_to=int(t["max_seq"]))
    ctx.log(f"reference over {len(seqs)} requests, {gaps['tokens']} served tokens, took "
            f"{time.perf_counter() - t_ref:.1f} s")
    notes = {}
    if ctx.control:
        low = R.served_token_gaps(arch, ctx.key_seed, seqs, pad_to=int(t["max_seq"]),
                                  precision="fp8")
        notes["control"] = {"token_gap": low["widest_gap"]}
    short = sum(1 for r in s["counted"] if len(r.result.tokens) != r.max_new)
    lim = ctx.cell.limits
    checks = [
        # nothing compared is no evidence: no value, not correct
        {"name": "token_gap", "value": gaps["widest_gap"] if gaps["tokens"] else None,
         "limit": float(lim["token_gap"])},
        {"name": "never_finished", "value": float(len(s["never"])), "limit": 0.0},
        {"name": "short_answers", "value": float(short), "limit": 0.0},
    ]
    window = rec["t_close"] - rec["t_open"]
    layer = {
        "kind": "serve",
        "window_s": window,
        "queue_p95_ms": s["queue_p95_ms"],
        "prefill_span_s": rec["prefill_span_s"],
        "prefill_span_n": rec["prefill_span_n"],
        "decode_span_s": rec["decode_span_s"],
        "decode_span_n": rec["decode_span_n"],
        "prefill_tokens": rec["prefill_tokens"],
        "prefill_flops": rec["prefill_flops"],
        "decode_steps": rec["decode_steps"],
        "decode_flops": rec["decode_flops"],
        "decode_ideal_s": rec["decode_ideal_s"],
        "account_ok": rec["mismatch"] == 0,
        "peak_flops": ctx.peak.bf16_flops,
        "trace": trace,
    }
    return Outcome(
        end_to_end={"ttft_p95_ms": s["ttft_p95_ms"], "tpot_p95_ms": s["tpot_p95_ms"],
                    "setup_s": setup_s},
        layer=layer,
        checks=checks,
        attempted=len(s["counted"]) + len(s["never"]),
        failed=len(s["never"]),
        memory_peak_bytes=peak_bytes,
        trace=trace,
        compiles_in_window=ctx.compiles.count,
        notes=notes,
    )


def _plant(fault: str, engine) -> None:
    """Break the timed path underneath the harness (tests only)."""
    if fault != "altered_token":
        raise ValueError(f"unknown fault {fault!r}")
    decode = engine._jit_decode

    def altered(p, c, tok):
        c, logits = decode(p, c, tok)
        return c, logits.at[:, 7].add(1e4)

    engine._jit_decode = altered
