"""Serving launcher: continuous-batching inference on the adapt control plane.

Requests stream through a :class:`repro.serving.ServeSession` — either all at
once (closed-loop drain) or as an open-loop Poisson arrival process
(``--arrival-rate``), the traffic shape production SLOs are judged under.
Batch-width and shedding decisions are taken by the ``ADAPT/serving``
controller on the session control loop and render in the report next to every
measured timer (paper §3.3: parameters "chosen dynamically from performance
measurements").

    PYTHONPATH=src python -m repro.launch.serve --arch llama3.2-1b \\
        --requests 32 --target-decode-ms 50 --arrival-rate 8 --report
"""

from __future__ import annotations

import argparse
import json
import time

import jax
import numpy as np

from ..configs import ARCH_IDS, get_smoke_config
from ..models import model as M
from ..models.config import ArchConfig
from ..monitor import MetricsExporter, serving_payload
from ..serving import Request, ServeSession, ServiceLevel
from ..timing import TimingSession
from .cache import configure_compile_cache

__all__ = ["main", "run_serving"]


def run_serving(
    arch: str = "llama3.2-1b",
    n_requests: int = 16,
    prompt_len: int = 32,
    max_new: int = 8,
    *,
    n_slots: int = 8,
    block_size: int = 16,
    target_decode_ms: float | None = None,
    max_queue_delay_s: float | None = None,
    arrival_rate: float | None = None,
    seed: int = 0,
    session: TimingSession | None = None,
    cfg: ArchConfig | None = None,
) -> ServeSession:
    """Build a :class:`~repro.serving.ServeSession` and serve ``n_requests``.

    With ``arrival_rate`` (requests/second) the submissions follow an
    open-loop Poisson process driven against the wall clock — the engine keeps
    decoding in-flight requests between arrivals; otherwise everything is
    submitted upfront and drained.  ``cfg`` overrides the smoke config of
    ``arch`` (e.g. a published-width config cut in depth).  Returns the engine
    (stats, request rows, and its control loop's decision log attached).
    """
    cfg = cfg if cfg is not None else get_smoke_config(arch)
    params = M.init_params(cfg, jax.random.PRNGKey(seed))
    rng = np.random.default_rng(seed)
    engine = ServeSession(
        cfg, params,
        session=session,
        n_slots=n_slots,
        max_seq=prompt_len + max_new + 8,
        block_size=block_size,
        slo=ServiceLevel(
            target_decode_ms=target_decode_ms,
            max_queue_delay_s=max_queue_delay_s,
        ),
    )
    requests = [
        Request(rid, prompt=list(rng.integers(0, cfg.vocab_size, prompt_len)),
                max_new_tokens=max_new)
        for rid in range(n_requests)
    ]
    if arrival_rate is None:
        for req in requests:
            engine.submit(req)
        engine.run_until_idle()
        return engine

    offsets = np.cumsum(rng.exponential(1.0 / arrival_rate, size=n_requests))
    t0 = time.monotonic()
    pending = list(zip(offsets, requests))
    while pending or engine.queue_depth or engine.active_slots:
        now = time.monotonic() - t0
        while pending and pending[0][0] <= now:
            engine.submit(pending.pop(0)[1])
        engine.step()
        if pending and not engine.queue_depth and not engine.active_slots:
            # idle gap before the next arrival: sleep it off instead of spinning
            time.sleep(max(pending[0][0] - (time.monotonic() - t0), 0.0))
    return engine


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", choices=list(ARCH_IDS), default="llama3.2-1b")
    ap.add_argument("--requests", type=int, default=16)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--max-new", type=int, default=8)
    ap.add_argument("--slots", type=int, default=8)
    ap.add_argument("--block-size", type=int, default=16)
    ap.add_argument("--target-decode-ms", type=float, default=None)
    ap.add_argument("--max-queue-delay", type=float, default=None,
                    help="SLO: shed queued requests past this estimated wait (s)")
    ap.add_argument("--arrival-rate", type=float, default=None,
                    help="open-loop Poisson arrivals (requests/s); default: drain")
    ap.add_argument("--report", action="store_true")
    ap.add_argument("--metrics-textfile", default=None,
                    help="write the final Prometheus exposition here "
                         "(textfile-collector scrape path)")
    args = ap.parse_args(argv)
    configure_compile_cache()
    sess = TimingSession()
    with sess:
        engine = run_serving(
            args.arch, args.requests, args.prompt_len, args.max_new,
            n_slots=args.slots, block_size=args.block_size,
            target_decode_ms=args.target_decode_ms,
            max_queue_delay_s=args.max_queue_delay,
            arrival_rate=args.arrival_rate,
            session=sess,
        )
    print(json.dumps(engine.stats(), indent=1))
    if args.metrics_textfile:
        MetricsExporter(
            sess.db,
            control_loop=engine.control_loop,
            serving_fn=serving_payload(engine),
        ).write_textfile(args.metrics_textfile)
    if args.report:
        print(sess.report())
        print()
        print(sess.tree_report())
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
