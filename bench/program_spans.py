"""Device idle time of a traced training window split by the program's spans.

The program writes each timer window into the profiler's trace as a host span
named by its timer path, with a ``parent`` stat naming the enclosing path
(``"/"`` at the top).  This module reads the traced run's ``.xplane.pb``
again, takes the device ops as ``bench/trace.py`` does and the program's
spans (the host events that carry ``parent``), and hands both to
``trace.reduce_events``: each idle stretch goes to the innermost program span
open at that instant.  The spans are then grouped by what the program was
doing:

* ``data``: a ``PRESTEP/`` routine or a span under one (the data feed);
* ``step_host``: the train step's own window, its ``dispatch`` (enqueuing the
  compiled step) and its ``fetch`` (the metrics' copy to the host);
* ``step_device``: the step's ``wait``, ``block_until_ready`` on the device;
* ``loop``: anything else: other routines, the benchmark's among them, the
  bins, the iteration, or no span at all.

The four partition the idle time, so their shares add up to
``device_idle_share.train``.  A run whose program writes no such spans (an
older program) reads ``None``.
"""

from __future__ import annotations

import functools
import glob
import os

from bench import trace

__all__ = ["GROUPS", "group_of", "split_idle", "shares"]

STEP = "EVOL/trainer::train_step"
GROUPS = ("data", "step_host", "step_device", "loop")
_STEP_HOST = {STEP, f"{STEP}/dispatch", f"{STEP}/fetch"}
_STEP_DEVICE = f"{STEP}/wait"


def group_of(name: str, parents: dict[str, str]) -> str:
    """The group of a program span, from its own path or the nearest
    enclosing one (``parents``: span path -> ``parent`` stat)."""
    seen = set()
    while name not in seen:
        if name.startswith("PRESTEP/"):
            return "data"
        if name in _STEP_HOST:
            return "step_host"
        if name == _STEP_DEVICE:
            return "step_device"
        if name not in parents:
            break
        seen.add(name)
        name = parents[name]
    return "loop"


def split_idle(device_ops: dict[str, list[tuple[str, float, float]]],
               spans: list[tuple[str, str, float, float]],
               window: tuple[float, float]) -> dict:
    """``spans``: [(path, parent, start_s, end_s)] on the device ops' clock.
    Returns ``busy_s``, ``window_s``, the idle seconds of each group (averaged
    over the devices) and ``by_span``, the idle seconds of each innermost
    span path."""
    parents = {name: parent for name, parent, _, _ in spans}
    r = trace.reduce_events(device_ops, [(n, s, e) for n, _, s, e in spans], window, top=None)
    idle = dict.fromkeys(GROUPS, 0.0)
    for name, seconds in r["idle_gaps"]:
        idle[group_of(name, parents)] += seconds
    return {"busy_s": r["busy_s"], "window_s": r["window_s"], "idle_s": idle,
            "by_span": dict(r["idle_gaps"])}


def _read(path: str, n_devices: int):
    from jax.profiler import ProfileData

    device_ops: dict[str, list] = {}
    spans, windows = [], []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/device:TPU:") and "SparseCore" not in plane.name:
            for line in plane.lines:
                if line.name == trace.OPS_LINE:
                    device_ops[plane.name] = [
                        (ev.name, ev.start_ns * 1e-9, (ev.start_ns + ev.duration_ns) * 1e-9)
                        for ev in line.events
                    ]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    start, end = ev.start_ns * 1e-9, (ev.start_ns + ev.duration_ns) * 1e-9
                    if ev.name == trace.WINDOW_SPAN:
                        windows.append((start, end))
                        continue
                    parent = dict(ev.stats).get("parent")
                    if parent is not None:
                        spans.append((ev.name, str(parent), start, end))
    keep = sorted(device_ops, key=lambda n: int(n.rsplit(":", 1)[-1]))[:n_devices]
    return {d: device_ops[d] for d in keep}, spans, windows


@functools.lru_cache(maxsize=4)
def _split_file(path: str, mtime_ns: int, n_devices: int) -> dict | None:
    device_ops, spans, windows = _read(path, n_devices)
    if not device_ops or not spans or not windows:
        return None
    return split_idle(device_ops, spans, windows[-1])


def _newest_trace() -> str | None:
    from bench.harness import CACHE_DIR

    files = glob.glob(os.path.join(str(CACHE_DIR / "trace"), "**", "*.xplane.pb"),
                      recursive=True)
    return max(files, key=os.path.getmtime) if files else None


def shares(outcome) -> dict[str, float] | None:
    """Each group's idle time in % of the traced window of ``outcome``'s run,
    or ``None`` where the run was not traced on a chip, its trace is not the
    newest file or holds no program span."""
    tr = outcome.trace
    if not tr or tr["window_s"] <= 0:
        return None
    path = _newest_trace()
    if path is None:
        return None
    split = _split_file(path, os.stat(path).st_mtime_ns, int(outcome.layer["chips"]))
    if split is None or abs(split["window_s"] - tr["window_s"]) > 1e-6:
        return None
    return {g: 100.0 * s / split["window_s"] for g, s in split["idle_s"].items()}
