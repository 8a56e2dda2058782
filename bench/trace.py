"""Reduce a profiler trace to device busy time, top device ops and idle gaps.

The JAX profiler writes ``<dir>/plugins/profile/<run>/<host>.xplane.pb``;
``jax.profiler.ProfileData`` reads it.  Device planes are named
``/device:TPU:<n>``; their ``XLA Ops`` line holds one event per operation
run on the chip.  The host plane (``/host:CPU``) holds the benchmark's own
``bench/...`` spans (``jax.profiler.TraceAnnotation``); ``bench/traced_window``
marks the traced part of the window.

* busy: the union of the op intervals of each device inside the window,
  averaged over the devices the cell uses, and each device's own (in the
  order of the devices' names);
* hand-off: the union of the intervals of the ops that pass activations and
  their gradients between pipeline stages (``collective-permute`` in the op's
  name) inside the window, averaged over the devices; ``None`` where the
  window holds none;
* device ops: each op name's summed time inside the window, averaged over the
  devices, the ten largest;
* idle gaps: each stretch of the window with no op on a device, split over
  the ``bench/`` host spans open at the time (the innermost one at each
  instant, ``none`` where no span is open), summed per span name, averaged
  over the devices, the ten largest.
"""

from __future__ import annotations

import glob
import os
from collections import defaultdict

__all__ = ["reduce_dir", "reduce_file", "reduce_events", "union_length"]

OPS_LINE = "XLA Ops"
WINDOW_SPAN = "bench/traced_window"
HANDOFF_OP = "collective-permute"


def union_length(intervals: list[tuple[float, float]]) -> float:
    total, end = 0.0, float("-inf")
    for s, e in sorted(intervals):
        if e <= end:
            continue
        total += e - max(s, end)
        end = e
    return total


def _gaps(intervals, lo, hi):
    out, cur = [], lo
    for s, e in sorted(intervals):
        if s > cur:
            out.append((cur, min(s, hi)))
        cur = max(cur, e)
        if cur >= hi:
            break
    if cur < hi:
        out.append((cur, hi))
    return [(s, e) for s, e in out if e > s]


def _attribute(gap, spans, into) -> None:
    """Split an idle gap over the host spans open during it: each stretch goes
    to the innermost (shortest) span covering it, ``none`` where none does."""
    lo, hi = gap
    over = [(n, s, e) for n, s, e in spans if e > lo and s < hi]
    cuts = sorted({lo, hi} | {t for _, s, e in over for t in (s, e) if lo < t < hi})
    for a, b in zip(cuts, cuts[1:]):
        mid = (a + b) / 2
        covering = [(e - s, n) for n, s, e in over if s <= mid < e]
        into[min(covering)[1] if covering else "none"] += b - a


def reduce_events(device_ops: dict[str, list[tuple[str, float, float]]],
                  host_spans: list[tuple[str, float, float]],
                  window: tuple[float, float] | None = None, top: int = 10) -> dict:
    """``device_ops``: {device: [(op, start_s, end_s)]}; ``host_spans``:
    [(name, start_s, end_s)] on the same clock; ``window``: (start_s, end_s).
    """
    devices = sorted(device_ops)
    if window is None:
        starts = [s for d in devices for _, s, _ in device_ops[d]]
        ends = [e for d in devices for _, _, e in device_ops[d]]
        window = (min(starts), max(ends))
    lo, hi = window
    spans = [(n, s, e) for n, s, e in host_spans if n != WINDOW_SPAN]
    busy, per_op, idle = 0.0, defaultdict(float), defaultdict(float)
    busy_by_device, handoff, handoff_ops = [], 0.0, 0
    for d in devices:
        clipped = [(n, max(s, lo), min(e, hi)) for n, s, e in device_ops[d] if e > lo and s < hi]
        iv = [(s, e) for _, s, e in clipped]
        busy_by_device.append(union_length(iv))
        busy += busy_by_device[-1]
        passes = [(s, e) for n, s, e in clipped if HANDOFF_OP in n]
        handoff += union_length(passes)
        handoff_ops += len(passes)
        for n, s, e in clipped:
            per_op[n] += e - s
        for g in _gaps(iv, lo, hi):
            _attribute(g, spans, idle)
    k = max(len(devices), 1)
    rank = lambda d: sorted(([n, v / k] for n, v in d.items()), key=lambda x: -x[1])[:top]  # noqa: E731
    return {
        "busy_s": busy / k,
        "busy_s_by_device": busy_by_device,
        "handoff_s": handoff / k if handoff_ops else None,
        "window_s": hi - lo,
        "device_ops": rank(per_op),
        "idle_gaps": rank(idle),
        "devices": len(devices),
    }


def reduce_file(path: str, n_devices: int | None = None) -> dict:
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    device_ops: dict[str, list] = {}
    host_spans: list = []
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:") and "SparseCore" not in plane.name:
            for line in plane.lines:
                if line.name == OPS_LINE:
                    device_ops[plane.name] = [
                        (ev.name, ev.start_ns * 1e-9, (ev.start_ns + ev.duration_ns) * 1e-9)
                        for ev in line.events
                    ]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith("bench/"):
                        host_spans.append(
                            (ev.name, ev.start_ns * 1e-9, (ev.start_ns + ev.duration_ns) * 1e-9))
    if not device_ops:
        raise ValueError(f"{path}: no {OPS_LINE!r} line on any TPU plane")
    if n_devices is not None:
        keep = sorted(device_ops, key=lambda n: int(n.rsplit(":", 1)[-1]))[:n_devices]
        device_ops = {d: device_ops[d] for d in keep}
    windows = [(s, e) for n, s, e in host_spans if n == WINDOW_SPAN]
    return reduce_events(device_ops, host_spans, windows[-1] if windows else None)


def reduce_dir(path, n_devices: int | None = None) -> dict:
    files = sorted(glob.glob(os.path.join(str(path), "**", "*.xplane.pb"), recursive=True))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {path}")
    return reduce_file(files[-1], n_devices)
