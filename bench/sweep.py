#!/usr/bin/env python3
"""Find a serving cell's knee: the highest arrival rate it sustains.

    python3 bench/sweep.py --workload serve.glm4-9b.chat --rates 3,4,5,6,7,8 --seconds 20

One engine (weights from ``--seed``), warmed once, then the cell's traffic at
each rate in turn, ``--seconds`` of window each.  Prints one JSON line per
rate: completed requests per second, the p95 latencies, and how many of the
window's requests were still queued at its close (a backlog that grows with
the window means the rate is past the knee).  The cell's rate is then fixed
in its traffic file at about 0.8 of the knee; the benchmark's runs never
search for one.
"""

import argparse
import json
import os
import sys
import time

sys.path[0] = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

from bench import harness  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--seed", type=int, default=4_000_000_000)
    args = ap.parse_args(argv)

    cell = harness.load_cell(args.workload)
    devices = harness.chips_for(cell)
    harness.configure_compile_cache()
    sys.path.insert(0, str(harness.ROOT / "src"))
    from bench.drivers import serve
    from bench.peaks import peak_for
    from bench.traffic import serve_arrivals

    ctx = harness.Context(cell=cell, seed=args.seed, seconds=args.seconds,
                          devices=devices, peak=peak_for(devices[0].device_kind),
                          t_start=time.perf_counter(), compiles=harness.CompileCounter())
    engine, sess, cfg, arch, dims = serve.build(ctx)
    serve.warm(engine, cell.traffic, cfg.vocab_size, args.seed)
    for rate in (float(r) for r in args.rates.split(",")):
        t = dict(cell.traffic, rate_rps=rate)
        segments = [float(t["lead_s"]), args.seconds, float(t["tail_arrivals_s"])]
        arrivals = serve_arrivals(t, args.seed, segments, cfg.vocab_size)
        rec = serve.drive(ctx, engine, sess, arrivals, t, dims)
        # the next rate starts from an empty engine
        engine.shed(engine.queue_depth)
        engine.run_until_idle()
        s = serve.summarize(rec)
        window = rec["t_close"] - rec["t_open"]
        finished_in_window = sum(
            1 for r in rec["done"] if rec["t_open"] <= r.result.finished_at < rec["t_close"])
        backlog = sum(1 for r in s["counted"] + s["never"]
                      if r.result is None or r.result.admitted_at > rec["t_close"])
        print(json.dumps({
            "rate_rps": rate, "arrived": len(s["counted"]) + len(s["never"]),
            "finished_per_s": finished_in_window / window,
            "tokens_per_s": sum(len(r.result.tokens) for r in rec["done"]
                                if rec["t_open"] <= r.result.finished_at < rec["t_close"]) / window,
            "ttft_p95_ms": s["ttft_p95_ms"], "tpot_p95_ms": s["tpot_p95_ms"],
            "queue_p95_ms": s["queue_p95_ms"], "backlog_at_close": backlog,
            "never_finished": len(s["never"]), "decode_steps": rec["decode_steps"],
            "decode_step_ms": 1e3 * rec["decode_span_s"] / max(rec["decode_span_n"], 1),
            "lag_p95_ms": s["lag_p95_ms"],
        }), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
