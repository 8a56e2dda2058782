"""The data-driven harness behind ``bench/run.py``.

Everything that belongs to one cell is found by name:

* the cell, its end-to-end and per-layer metrics: ``BENCHMARK.json``;
* its configuration: the ``file`` of its ``configs`` entry
  (``bench/configs/<config>.json``), whose ``reference`` key names its block
  family (``bench/families/<reference>.py``: the keys, the program's and the
  reference's configs, the counts) and the plain reference module
  (``bench/reference/<reference>.py``);
* its traffic: ``bench/traffic/<traffic>.json``, which names the driver that
  reads it (``bench/drivers/<driver>.py``);
* its correctness limits: ``bench/limits/<workload>.json``;
* each per-layer metric: ``bench/metrics/<metric>.py`` with
  ``read(outcome) -> float | None`` (``None``: nothing to read, left out).

A driver's ``run(ctx)`` returns an :class:`Outcome`; the harness turns it into
the one JSON line the benchmark prints last.
"""

from __future__ import annotations

import importlib
import importlib.util
import json
import math
import os
import sys
import time
from dataclasses import dataclass, field
from pathlib import Path
from typing import Any

ROOT = Path(__file__).resolve().parents[1]
#: JAX's persistent compilation cache and profiler traces: a fixed path inside
#: the checkout, so every run of a checkout finds what its first run compiled
CACHE_DIR = ROOT / ".bench_cache"

__all__ = ["Cell", "Context", "Outcome", "NoChip", "load_cell", "draft_cell", "family_module",
           "run_cell", "emit", "main"]


class NoChip(RuntimeError):
    """JAX found no accelerator, or fewer chips than the cell asks for."""


@dataclass
class Cell:
    name: str
    chips: int
    config_name: str
    config: dict
    traffic: dict
    limits: dict
    end_to_end: list[dict]
    per_layer: list[dict]


def _read_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(name: str, root: Path = ROOT) -> Cell:
    bench = _read_json(root / "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"unknown workload {name!r}; BENCHMARK.json has {sorted(cells)}")
    w = cells[name]
    conf = {c["name"]: c for c in bench["configs"]}[w["config"]]
    return Cell(
        name=name,
        chips=int(w["chips"]),
        config_name=w["config"],
        config=_read_json(root / conf["file"]),
        traffic=_read_json(root / "bench" / "traffic" / f"{w['traffic']}.json"),
        limits=_read_json(root / "bench" / "limits" / f"{name}.json"),
        end_to_end=[m for m in bench["end_to_end"] if _applies(m, name)],
        per_layer=[m for m in bench["per_layer"] if _applies(m, name)],
    )


def draft_cell(name: str, config: str, traffic: str, chips: int = 1, root: Path = ROOT) -> Cell:
    """A cell that ``BENCHMARK.json`` does not hold yet, from its files alone
    (configuration, traffic and, once they are set, limits), with no metrics:
    for the tools and tests that run a mix before it has bounds."""
    bench = root / "bench"
    limits = bench / "limits" / f"{name}.json"
    return Cell(
        name=name, chips=chips, config_name=config,
        config=_read_json(bench / "configs" / f"{config}.json"),
        traffic=_read_json(bench / "traffic" / f"{traffic}.json"),
        limits=_read_json(limits) if limits.is_file() else {},
        end_to_end=[], per_layer=[],
    )


def _load_file(path: Path, name: str):
    if name in sys.modules:
        return sys.modules[name]
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


def driver_module(cell: Cell, root: Path = ROOT):
    return _load_file(root / "bench" / "drivers" / f"{cell.traffic['driver']}.py",
                      f"bench_driver_{cell.traffic['driver']}")


def metric_reader(name: str, root: Path = ROOT):
    return _load_file(root / "bench" / "metrics" / f"{name}.py",
                      "bench_metric_" + name.replace(".", "_").replace("-", "_"))


def reference_module(cell: Cell):
    return importlib.import_module(f"bench.reference.{cell.config['reference']}")


def family_module(cell: Cell, root: Path = ROOT):
    """The configuration's block family, ``bench/families/<reference>.py``."""
    family = cell.config["reference"]
    path = root / "bench" / "families" / f"{family}.py"
    if not path.is_file():
        raise FileNotFoundError(f"{cell.config_name} names the family {family!r}: no {path}")
    return _load_file(path, f"bench.families.{family}")


def jax_seed(seed: int) -> int:
    """A 31-bit key seed for ``jax.random.PRNGKey`` from any whole ``--seed``."""
    import numpy as np

    return int(np.random.SeedSequence(int(seed)).generate_state(1, np.uint32)[0] >> 1)


# ---------------------------------------------------------------------------
# What a run hands the drivers
# ---------------------------------------------------------------------------

class CompileCounter:
    """Counts lowerings and backend compiles while open (JAX monitoring
    events): a program traced or compiled inside the measured window."""

    EVENTS = (
        "/jax/core/compile/jaxpr_to_mlir_module_duration",
        "/jax/core/compile/backend_compile_duration",
    )

    def __init__(self) -> None:
        import jax

        self.open = False
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event: str, duration: float, **kwargs) -> None:
        if self.open and event in self.EVENTS:
            self.count += 1


class Tracer:
    """The profiler over part of the window, reduced by ``bench/trace.py``."""

    def __init__(self, workload: str, on_chip: bool = True) -> None:
        self.dir = CACHE_DIR / "trace" / workload
        self.on_chip = on_chip
        self.active = False
        self._ann = None

    def start(self) -> None:
        import shutil

        import jax

        shutil.rmtree(self.dir, ignore_errors=True)
        # ops and spans only: the Python tracer records every call, which
        # slows a host-bound loop such as the serving engine's several times
        # over
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        jax.profiler.start_trace(str(self.dir), profiler_options=options)
        self._ann = jax.profiler.TraceAnnotation("bench/traced_window")
        self._ann.__enter__()
        self.active = True

    def stop(self) -> None:
        import jax

        self._ann.__exit__(None, None, None)
        jax.profiler.stop_trace()
        self.active = False

    def reduce(self, n_devices: int) -> dict | None:
        """The trace's reduction; ``None`` off the chip, where no TPU plane is."""
        from bench import trace

        if not self.on_chip:
            return None
        return trace.reduce_dir(self.dir, n_devices=n_devices)


@dataclass
class Context:
    cell: Cell
    seed: int
    seconds: float
    devices: list
    peak: Any
    t_start: float
    compiles: CompileCounter | None = None
    tracer: Tracer | None = None
    #: a deliberately broken timed path (tests only): see drivers
    fault: str | None = None
    #: also compute the control's readings (``bench/readings.py``)
    control: bool = False

    @property
    def key_seed(self) -> int:
        return jax_seed(self.seed)

    def log(self, *parts) -> None:
        print("[bench]", *parts, file=sys.stderr, flush=True)


@dataclass
class Outcome:
    end_to_end: dict[str, float]
    layer: dict[str, Any]
    checks: list[dict]
    attempted: int
    failed: int
    memory_peak_bytes: int
    trace: dict | None = None
    compiles_in_window: int = 0
    notes: dict = field(default_factory=dict)

    @property
    def correct(self) -> bool:
        return all(
            c["value"] is not None and math.isfinite(c["value"]) and c["value"] <= c["limit"]
            for c in self.checks
        ) and bool(self.checks)


def memory_peak(devices) -> int:
    peaks = []
    for d in devices:
        stats = d.memory_stats() or {}
        peaks.append(int(stats.get("peak_bytes_in_use", 0)))
    return max(peaks) if peaks else 0


def configure_compile_cache() -> str:
    import jax

    path = str(CACHE_DIR / "jax")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def chips_for(cell: Cell, require_chip: bool = True) -> list:
    import jax

    devices = jax.devices()
    if require_chip:
        if devices[0].platform != "tpu":
            raise NoChip(f"JAX found no TPU (platform {devices[0].platform!r})")
        if len(devices) < cell.chips:
            raise NoChip(f"{cell.name} needs {cell.chips} chips; JAX found {len(devices)}")
    return devices[: cell.chips]


def run_cell(
    name: str,
    seed: int,
    seconds: float,
    trace: bool,
    *,
    t_start: float | None = None,
    require_chip: bool = True,
    cell: Cell | None = None,
    peak=None,
    fault: str | None = None,
    control: bool = False,
    outcome: list | None = None,
) -> dict:
    """Run one cell; return the result line's object (``checks`` last)."""
    t_start = time.perf_counter() if t_start is None else t_start
    cell = cell if cell is not None else load_cell(name)
    devices = chips_for(cell, require_chip)
    if require_chip:
        configure_compile_cache()
    if str(ROOT / "src") not in sys.path:
        sys.path.insert(0, str(ROOT / "src"))
    if peak is None:
        from bench.peaks import peak_for

        peak = peak_for(devices[0].device_kind)
    ctx = Context(
        cell=cell, seed=seed, seconds=seconds, devices=devices, peak=peak,
        t_start=t_start, compiles=CompileCounter(),
        tracer=Tracer(cell.name, require_chip) if trace else None, fault=fault,
        control=control,
    )
    out: Outcome = driver_module(cell).run(ctx)
    if outcome is not None:
        outcome.append(out)
    ctx.log(f"compilations inside the window: {out.compiles_in_window}")

    metrics: dict[str, dict] = {}
    if not trace:
        for m in cell.end_to_end:
            value = out.end_to_end.get(m["name"])
            if value is None:
                raise RuntimeError(f"{cell.name}: the driver measured no {m['name']}")
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        for m in cell.per_layer:
            value = metric_reader(m["name"]).read(out)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    device = {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
        "memory_peak_bytes": out.memory_peak_bytes,
    }
    result: dict[str, Any] = {
        "correct": out.correct,
        "attempted": out.attempted,
        "failed": out.failed,
        "metrics": metrics,
        "device": device,
    }
    if trace and out.trace is not None:
        device["busy_s"] = out.trace["busy_s"]
        device["window_s"] = out.trace["window_s"]
        result["breakdown"] = {
            "device_ops": out.trace["device_ops"],
            "idle_gaps": out.trace["idle_gaps"],
        }
    result["checks"] = {
        c["name"]: {"value": c["value"], "limit": c["limit"]} for c in out.checks
    }
    return result


def emit(result: dict) -> None:
    for name, c in result["checks"].items():
        print(f"check {name} = {c['value']!r} (limit {c['limit']!r})", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)


def main(argv=None) -> int:
    import argparse

    t_start = time.perf_counter()
    ap = argparse.ArgumentParser(description="Run one benchmark cell on the chip.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        result = run_cell(args.workload, args.seed, args.seconds, bool(args.trace),
                          t_start=t_start)
    except NoChip as e:
        print(f"[bench] {e}", file=sys.stderr)
        return 3
    emit(result)
    return 0
