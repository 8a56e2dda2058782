#!/usr/bin/env python3
"""Record the small chip traces that ``bench/tests`` reduce.

    python3 bench/record_trace.py <out_dir>           # small.xplane.pb
    python3 bench/record_trace.py <out_dir> --serve   # serve.xplane.pb

Without ``--serve``: five steps of a jitted bf16 matmul chain on one chip,
each followed by 20 ms of host sleep inside a ``bench/host_sleep`` span, all
inside ``bench/traced_window``.

With ``--serve``: the chat mix's own run (``bench/drivers/serve.py``) cut to
two of its configuration's layers at published widths and a 2 s window, of
which the driver traces the last ``SERVE_TRACE_S`` seconds: the engine's
``serve/prefill`` and ``serve/decode`` spans beside the device's ops.

Writes ``<out_dir>/<name>.xplane.pb`` without what no reader needs (the
``/host:metadata`` plane and the device ops' metadata stats, four fifths of a
serve trace) and prints the planes and lines the profiler wrote.  Needs a
TPU.
"""

import argparse
import glob
import json
import os
import shutil
import sys
import time

sys.path[0] = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

SERVE_TRACE_S = 0.08


def record_small(tmp: str) -> None:
    x = jnp.ones((2048, 2048), jnp.bfloat16)
    f = jax.jit(lambda x: jnp.tanh(x @ x @ x @ x))
    f(x).block_until_ready()
    jax.profiler.start_trace(tmp)
    with jax.profiler.TraceAnnotation("bench/traced_window"):
        for _ in range(5):
            with jax.profiler.TraceAnnotation("bench/step"):
                f(x).block_until_ready()
            with jax.profiler.TraceAnnotation("bench/host_sleep"):
                time.sleep(0.02)
    jax.profiler.stop_trace()


def record_serve(tmp: str) -> None:
    from bench import harness

    cell = harness.draft_cell("serve.glm4-9b.chat", "glm4-9b-serve", "chat")
    cell.config = dict(cell.config, num_layers=2)
    cell.traffic = dict(cell.traffic, lead_s=2, tail_arrivals_s=2, check_requests=2,
                        trace_seconds=SERVE_TRACE_S)
    result = harness.run_cell(cell.name, 2**31 + 101, 2.0, True, cell=cell)
    print(json.dumps({k: result[k] for k in ("correct", "metrics", "checks")}))
    shutil.copytree(harness.CACHE_DIR / "trace" / cell.name, tmp)


def _varint(b: bytes, i: int) -> tuple[int, int]:
    out = shift = 0
    while True:
        c = b[i]
        i += 1
        out |= (c & 0x7F) << shift
        shift += 7
        if c < 0x80:
            return out, i


def _fields(b: bytes):
    """(field number, payload of a length-delimited field or None, raw bytes)
    of each field of a serialized protobuf message."""
    i = 0
    while i < len(b):
        start = i
        tag, i = _varint(b, i)
        wire = tag & 7
        payload = None
        if wire == 0:
            _, i = _varint(b, i)
        elif wire == 2:
            n, i = _varint(b, i)
            payload, i = b[i:i + n], i + n
        else:
            i += {1: 8, 5: 4}[wire]
        yield tag >> 3, payload, b[start:i]


def _delimited(field: int, payload: bytes) -> bytes:
    out, n = [field << 3 | 2], len(payload)
    while n >= 0x80:
        out.append(n & 0x7F | 0x80)
        n >>= 7
    return bytes(out + [n]) + payload


def strip(data: bytes) -> bytes:
    """An ``XSpace`` without its ``/host:metadata`` plane (field 1 of the
    space) and without the stats (field 5) of each device plane's event
    metadata (field 4 of a plane, a map whose values are field 2): op names,
    times and the host spans' own stats stay."""
    out = []
    for f, plane, raw in _fields(data):
        name = next((p for g, p, _ in _fields(plane) if g == 2), b"") if f == 1 else b""
        if name == b"/host:metadata":
            continue
        if name.startswith(b"/device:"):
            kept = []
            for g, entry, graw in _fields(plane):
                if g == 4:
                    parts = []
                    for h, meta, hraw in _fields(entry):
                        if h == 2:
                            hraw = _delimited(2, b"".join(
                                r for k, _, r in _fields(meta) if k != 5))
                        parts.append(hraw)
                    graw = _delimited(4, b"".join(parts))
                kept.append(graw)
            raw = _delimited(1, b"".join(kept))
        out.append(raw)
    return b"".join(out)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("out_dir")
    ap.add_argument("--serve", action="store_true")
    args = ap.parse_args(argv)
    if jax.devices()[0].platform != "tpu":
        raise SystemExit("record_trace.py needs a TPU")
    name = "serve.xplane.pb" if args.serve else "small.xplane.pb"
    tmp = os.path.join(args.out_dir, "profile")
    shutil.rmtree(tmp, ignore_errors=True)
    (record_serve if args.serve else record_small)(tmp)
    out = os.path.join(args.out_dir, name)
    src = glob.glob(os.path.join(tmp, "**", "*.xplane.pb"), recursive=True)[0]
    with open(src, "rb") as f, open(out, "wb") as g:
        g.write(strip(f.read()))
    shutil.rmtree(tmp, ignore_errors=True)
    from jax.profiler import ProfileData

    for plane in ProfileData.from_file(out).planes:
        lines = {line.name: sum(1 for _ in line.events) for line in plane.lines}
        print(json.dumps({"plane": plane.name, "lines": lines}))
    print(json.dumps({"bytes": os.path.getsize(out), "kind": jax.devices()[0].device_kind}))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
