"""Training launcher: the scheduler-driven loop with the full timing
infrastructure, a unified runtime-adaptation control plane, restart, and
monitoring.

This is the production driver (examples/train_llm.py calls ``run_training``):
every lifecycle phase is a scheduled routine in a Cactus-style bin, so the
timer database holds a complete profile with zero manual instrumentation.  All
runtime adaptation goes through ONE :class:`repro.adapt.ControlLoop` polled
from the ANALYSIS bin: AdaptCheck checkpoint admission (paper §3.2, via
:class:`repro.adapt.CheckpointControl`) and straggler response
(:class:`repro.adapt.StragglerResponse` over the cross-host step-time
reduction).  Every decision lands in the ``ADAPT/`` section of the timer
report.

    PYTHONPATH=src python -m repro.launch.train --arch llama3.2-1b --smoke \
        --steps 50 --batch 8 --seq 128 --ckpt-dir /tmp/ckpt
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import time
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from ..adapt import CheckpointControl, ControlLoop, StragglerResponse
from ..checkpoint import CheckpointManager
from ..configs import ARCH_IDS, get_config, get_smoke_config
from ..core import (
    AdaptiveCheckpointController,
    AdaptiveCheckpointPolicy,
    RunState,
    TimerLogger,
    adapt_rows,
    bin_distribution,
    format_report,
    format_tree_report,
    param_registry,
    straggler_rows,
    timer_db,
    tree_rows,
)
from ..core.clocks import CounterClock, register_clock
from ..data import DataLoader, SyntheticConfig, SyntheticLM
from ..dist.meshutil import local_mesh
from ..dist.pipeline import MicrobatchPlan, StagePlan, phase_ticks
from ..dist.stragglers import StragglerDetector
from ..fleet.topology import stage_for_host
from ..models import model as M, pipeline as model_pipeline
from ..models.config import ArchConfig, ShapeConfig
from ..monitor import MetricsExporter, MonitorServer, StatusWriter
from ..optim import AdamWConfig, init_opt_state
from ..timing import TimingSession
from .cache import configure_compile_cache
from .steps import (
    make_pipeline_train_step,
    make_train_step,
    make_transformer_pipeline_train_step,
    rules_for,
)

__all__ = ["TrainSettings", "run_training", "main"]


@dataclasses.dataclass
class TrainSettings:
    arch: str = "llama3.2-1b"
    smoke: bool = True
    steps: int = 50
    global_batch: int = 8
    seq_len: int = 128
    mesh_shape: tuple = (1, 1)
    peak_lr: float = 1e-3
    ckpt_dir: str | None = None
    ckpt_mode: str = "adaptive"          # "adaptive" | "fixed" | "off"
    ckpt_every: int = 512                # fixed mode
    ckpt_max_fraction: float = 0.05      # adaptive mode
    ckpt_max_interval_s: float = 60.0
    ckpt_synchronous: bool = False
    ckpt_delay_s: float = 0.0            # injected write latency (experiments)
    ckpt_keep_n: int = 3                 # retention: keep the newest N
    ckpt_keep_every_k: int = 0           # retention: also keep step % k == 0
    #: preemption grace period: SIGTERM triggers a deadline-bounded emergency
    #: save through the manager's chained handler (None installs no deadline)
    save_deadline_s: float | None = None
    queue_seconds: float | None = None
    eval_every: int = 0
    report_every: int = 25
    log_path: str | None = None
    status_path: str | None = None
    monitor_port: int | None = None
    #: Prometheus textfile-collector path: the exporter page is atomically
    #: rewritten on the report cadence and at shutdown (node_exporter style)
    metrics_textfile: str | None = None
    restore: bool = True
    seed: int = 0
    data_mode: str = "copy"
    #: LR-schedule horizon; decoupled from `steps` so an interrupted run and
    #: its resumption share the same schedule (restart determinism)
    lr_total_steps: int | None = None
    #: pipeline-parallel (1F1B) training path: 0 = off; N > 0 shards stages
    #: over an N-way "pod" mesh axis (N must not exceed visible devices; the
    #: CPU smoke path uses 1 and still runs the full tick schedule)
    pipeline_stages: int = 0
    pipeline_layers: int = 8          # homogeneous stage-stack depth (MLP path)
    pipeline_micro: int = 4           # 1F1B microbatch count
    pipeline_width: int = 32          # stage activation width (MLP path)
    #: pipeline the real transformer (cfg's block stack; one pattern period
    #: per slot, embed/head pinned to the end stages) instead of the
    #: synthetic residual-MLP stack
    pipeline_model: bool = False


def _flops_per_step(cfg: ArchConfig, tokens: int) -> float:
    _, active = M.param_counts(cfg)
    return 6.0 * active * tokens


def _bytes_by_device(tree) -> dict[int, int]:
    """Bytes of ``tree``'s device arrays held on each device id (replicas
    counted on every device that holds one; host leaves are not counted)."""
    out: dict[int, int] = {}
    for leaf in jax.tree.leaves(tree):
        for shard in getattr(leaf, "addressable_shards", ()):
            out[shard.device.id] = out.get(shard.device.id, 0) + shard.data.nbytes
    return dict(sorted(out.items()))


def run_training(
    settings: TrainSettings,
    cfg: ArchConfig | None = None,
    control_loop: ControlLoop | None = None,
    session: TimingSession | None = None,
) -> dict[str, Any]:
    """Run the scheduled training loop; returns a summary dict.

    ``session`` supplies the whole timing stack (database + scheduler +
    control loop) as one :class:`repro.timing.TimingSession`; by default the
    launcher bundles one over the process-global database, so a bare call
    still profiles into ``timer_db()``.  ``control_loop`` remains the narrower
    injection point (e.g. extra custom controllers pre-registered, or to
    inspect the decision log afterwards) and is ignored when a session is
    passed — register controllers on ``session.control_loop`` instead.
    """
    sess = (
        session
        if session is not None
        else TimingSession(timer_db(), control_loop=control_loop)
    )
    db = sess.db
    registry = param_registry()
    sch = sess.scheduler
    st = RunState(max_iterations=settings.steps)
    # checkpoint-label convention: every save is labeled with the number of
    # optimizer updates applied — i.e. the next iteration to execute — so a
    # resume (`s.iteration = label; DataLoader(start_step=label)`) replays the
    # trajectory exactly.  The CHECKPOINT bin of iteration i runs *after*
    # EVOL applied update i, so its label is i + 1, never i.
    st["updates"] = 0

    if cfg is None:
        cfg = get_smoke_config(settings.arch) if settings.smoke else get_config(settings.arch)
    pipelined = settings.pipeline_stages > 0
    if pipelined:
        # the 1F1B path pipelines homogeneous stages over a dedicated pod axis
        mesh = local_mesh((settings.pipeline_stages,), ("pod",))
    else:
        mesh = local_mesh(settings.mesh_shape)
    rules = rules_for(cfg)
    shape = ShapeConfig("train_local", "train", settings.seq_len, settings.global_batch)

    registry.declare("ckpt.max_fraction", settings.ckpt_max_fraction, steerable=True,
                     doc="AdaptCheck wall-time fraction bound")
    registry.declare("ckpt.max_interval_s", settings.ckpt_max_interval_s, steerable=True,
                     doc="AdaptCheck max seconds between checkpoints")

    # --- thorn state shared across routines -------------------------------------
    manager = None
    logger = TimerLogger(settings.log_path, db=db) if settings.log_path else None
    status = StatusWriter(settings.status_path) if settings.status_path else None
    monitor = None
    if pipelined and not settings.pipeline_model:
        # the MLP pipeline path trains the residual-MLP stage stack, not the
        # transformer cfg: same 6 * active-params * tokens convention, with
        # the stack's actual parameter count (n_layers x 2 W x W matmuls)
        active = settings.pipeline_layers * 2 * settings.pipeline_width ** 2
        model_flops = 6.0 * active * settings.global_batch * settings.seq_len
    else:
        model_flops = _flops_per_step(cfg, settings.global_batch * settings.seq_len)

    # --- the control plane: one loop, every adaptation registered on it ----------
    ckpt_timer_name = "CHECKPOINT/adaptcheck::write"
    ckpt_write_scope = sess.scope_handle(ckpt_timer_name)
    loop = sess.control_loop
    policy = AdaptiveCheckpointPolicy(
        mode="adaptive" if settings.ckpt_mode == "adaptive" else "fixed",
        every_iterations=settings.ckpt_every,
        max_fraction=registry.get("ckpt.max_fraction"),
        max_interval_seconds=registry.get("ckpt.max_interval_s"),
        queue_seconds=settings.queue_seconds,
    )
    controller = AdaptiveCheckpointController(policy)
    ckpt_control = CheckpointControl(
        controller, ckpt_timer=ckpt_timer_name, registry=registry
    )
    ckpt_active = bool(settings.ckpt_dir) and settings.ckpt_mode != "off"
    if ckpt_active:
        loop.register(ckpt_control)

    def topology_meta() -> dict[str, Any]:
        """Topology block stamped into every checkpoint's metadata — what a
        resume into a *different* host/stage count re-apportions from."""
        if not pipelined:
            return {}
        return {
            "topology": {
                "n_layers": stage_plan.n_layers,
                "n_micro": settings.pipeline_micro,
                "stage_weights": {
                    int(k): float(v) for k, v in stage_plan.weights.items()
                },
            }
        }

    def current_state() -> dict[str, Any]:
        return {
            "params": st["params"],
            "opt_state": st["opt_state"],
            "data": st["loader"].state(),
        }

    def durable_save(step: int) -> float:
        """Checkpoint-before-evict: write AND wait until durable (the barrier
        contract — an eviction must never outrun its safety checkpoint)."""
        if manager is None:
            raise RuntimeError("no checkpoint manager bound")
        t0 = time.monotonic()
        with ckpt_write_scope:
            # labeled with the update count, not the adapt step: the barrier
            # fires post-EVOL, so the state on disk is the start-of-step state
            # for update `st["updates"]` (see adaptive_checkpoint)
            manager.save(
                st["updates"], current_state(),
                metadata={"reason": "before_evict", **topology_meta()},
            )
            manager.wait()
        return time.monotonic() - t0

    if ckpt_active:
        ckpt_control.bind_durable_save(durable_save)
    # single-process topology: this host feeds its own EVOL step timer into the
    # reduction; multi-host launchers hand the detector a transport instead and
    # every host publishes through it.  On the pipeline path the response
    # controller additionally owns the StagePlan, so a confirmed straggler
    # that owns a stage is answered by moving the stage boundary (restage)
    # before any microbatch derate.
    pipeline_units = (
        model_pipeline.check_pipelineable(cfg)
        if pipelined and settings.pipeline_model
        else settings.pipeline_layers
    )
    stage_plan = (
        StagePlan.equal(range(settings.pipeline_stages), pipeline_units)
        if pipelined
        else None
    )
    detector = StragglerDetector(n_hosts=1, db=db)
    loop.register(
        StragglerResponse(
            detector,
            MicrobatchPlan.equal([0], n_micro=1),
            check_every=8,
            local_feed=(0, "EVOL/trainer::train_step"),
            stage_plan=stage_plan,
            # stage ownership derived from membership coordinates, not
            # hard-coded: one live host on an S-stage pipeline owns stage 0
            # (the rest ride along in-process), and a multi-host launcher
            # passes its real membership through the same function
            stage_for_host=(
                stage_for_host([0], settings.pipeline_stages) if pipelined else None
            ),
            evict_barrier=ckpt_control.evict_barrier if ckpt_active else None,
        )
    )
    sch.attach_control_loop(loop, bin="ANALYSIS")
    # one exporter for both surfaces: the monitor's /metrics endpoint and the
    # optional textfile written on the report cadence
    exporter = MetricsExporter(
        db, control_loop=loop, detector=detector,
        checkpoint_fn=lambda: manager.status_payload() if manager is not None else {},
    )
    # training-event clock registered mid-run (the paper's extensibility path:
    # every timer picks it up from its next window) + lock-free channel cells
    # resolved once for the hot loop
    register_clock(
        "events",
        lambda: CounterClock("events", {"tokens": "count", "steps": "count"}),
    )
    bump_flops = sess.counter("xla_flops", absolute=True)
    bump_tokens = sess.counter("tokens", absolute=True)
    bump_steps = sess.counter("steps", absolute=True)

    # --- STARTUP ----------------------------------------------------------------
    def startup(s: RunState) -> None:
        nonlocal manager, monitor
        opt_cfg = AdamWConfig()
        horizon = settings.lr_total_steps or settings.steps
        if pipelined:
            # each schedule phase is a separately dispatched, synchronized
            # segment recorded under its own timing scope
            phase_handles = {
                name: sess.scope_handle(f"train/pipeline/{name}")
                for name in phase_ticks(settings.pipeline_micro,
                                        settings.pipeline_stages)
            }
            if settings.pipeline_model:
                built = make_transformer_pipeline_train_step(
                    cfg, mesh, stage_plan,
                    seq_len=settings.seq_len,
                    global_batch=settings.global_batch,
                    n_micro=settings.pipeline_micro,
                    rules=rules,
                    opt_cfg=opt_cfg,
                    peak_lr=settings.peak_lr, total_steps=max(horizon, 2),
                    warmup_steps=max(min(100, horizon // 10), 1),
                    seed=settings.seed,
                    phase_cb=lambda name: phase_handles[name],
                )
            else:
                built = make_pipeline_train_step(
                    mesh, stage_plan,
                    width=settings.pipeline_width,
                    vocab_size=cfg.vocab_size,
                    seq_len=settings.seq_len,
                    global_batch=settings.global_batch,
                    n_micro=settings.pipeline_micro,
                    opt_cfg=opt_cfg,
                    peak_lr=settings.peak_lr, total_steps=max(horizon, 2),
                    warmup_steps=max(min(100, horizon // 10), 1),
                    seed=settings.seed,
                    phase_cb=lambda name: phase_handles[name],
                )
            s["built"] = built
            s["exec"] = built.fn  # host-side: re-packs the live StagePlan
        else:
            built = make_train_step(
                cfg, mesh, rules, shape, opt_cfg=opt_cfg,
                peak_lr=settings.peak_lr, total_steps=max(horizon, 2),
                warmup_steps=max(min(100, horizon // 10), 1),
            )
            s["built"] = built
            # absolute-path scope: keeps the historical name while nesting
            # under the STARTUP driver routine in the tree report
            with sess.scope_handle("STARTUP/compile"):
                s["exec"] = built.fn.lower(
                    built.abstract_state["params"],
                    built.abstract_state["opt_state"],
                    *built.abstract_inputs,
                ).compile()

        source = SyntheticLM(
            SyntheticConfig(cfg.vocab_size, settings.seq_len, settings.global_batch,
                            mode=settings.data_mode, seed=settings.seed),
            arch=cfg,
        )
        start_step = 0
        restored = None
        if settings.ckpt_dir:
            manager = CheckpointManager(
                settings.ckpt_dir,
                keep_n=settings.ckpt_keep_n,
                keep_every_k=settings.ckpt_keep_every_k,
                synchronous=settings.ckpt_synchronous,
                delay_s=settings.ckpt_delay_s,
            )
            if settings.restore:
                restored = manager.restore_latest()
        if restored is not None:
            start_step, tree, meta = restored
            s["params"] = tree["params"]
            s["opt_state"] = tree["opt_state"]
            s.iteration = start_step
            s["updates"] = start_step
            topo = (meta or {}).get("topology")
            if (
                pipelined
                and topo
                and int(topo.get("n_layers", -1)) == stage_plan.n_layers
            ):
                # N->M topology restore: re-apportion the saved stage capacity
                # weights onto the *current* stage set.  The parameter stack is
                # flat per-layer, so adopting the retargeted weights in place
                # is all it takes — the next step's pack() splits the same
                # layers along the new boundaries.  (Manifest JSON stringifies
                # the stage keys; convert back.)
                saved = StagePlan(
                    n_layers=stage_plan.n_layers,
                    weights={
                        int(k): float(v)
                        for k, v in topo["stage_weights"].items()
                    },
                )
                adopted = saved.retarget(range(settings.pipeline_stages))
                stage_plan.weights.clear()
                stage_plan.weights.update(adopted.weights)
            print(f"[train] restored checkpoint at step {start_step}")
        else:
            with sess.scope_handle("STARTUP/init_params"):
                if pipelined:
                    s["params"] = built.init_params(
                        jax.random.PRNGKey(settings.seed)
                    )
                else:
                    s["params"] = M.init_params(cfg, jax.random.PRNGKey(settings.seed))
                s["opt_state"] = init_opt_state(AdamWConfig(), s["params"])
        # commit state to the mesh with the step's exact shardings (AOT path;
        # the pipeline path shards inside its shard_map'd tick runner)
        if built.in_shardings[0] is not None:
            s["params"] = jax.device_put(s["params"], built.in_shardings[0])
            s["opt_state"] = jax.device_put(s["opt_state"], built.in_shardings[1])
        s["loader"] = DataLoader(source, start_step=start_step)

        if manager is not None:
            # installed only once live state exists — a preemption mid-restore
            # has nothing durable to add anyway.  The label is the number of
            # optimizer updates applied so far, which is exact no matter where
            # in the scheduler cycle the signal lands.
            try:
                manager.install_sigterm_handler(
                    lambda: (st["updates"], current_state()),
                    deadline_s=settings.save_deadline_s,
                )
            except ValueError:
                pass  # not the main thread: signals unavailable, skip the hook

        ckpt_control.start_run(time.monotonic())
        if settings.monitor_port is not None:
            monitor = MonitorServer(settings.monitor_port, db, registry,
                                    status_fn=lambda: {"iteration": st.iteration},
                                    checkpoint_fn=(
                                        manager.status_payload
                                        if manager is not None else None
                                    ),
                                    exporter=exporter)
            port = monitor.start()
            print(f"[train] monitor at http://127.0.0.1:{port}/")
        registry.freeze()

    sch.schedule(startup, bin="STARTUP", thorn="driver")

    # --- PRESTEP: data ------------------------------------------------------------
    def fetch_data(s: RunState) -> None:
        batch = s["loader"].next()
        shardings = s["built"].in_shardings[2]

        def put(k, v):
            if v.dtype == np.float32:  # modality stubs arrive f32 -> bf16
                v = jnp.asarray(v, jnp.bfloat16)
            return jax.device_put(v, shardings[k])

        s["batch"] = {k: put(k, v) for k, v in batch.items()}

    sch.schedule(fetch_data, bin="PRESTEP", thorn="data")

    # --- EVOL: the jitted step -----------------------------------------------------
    def train_step(s: RunState) -> None:
        params, opt_state, metrics = s["exec"](s["params"], s["opt_state"], s["batch"])
        metrics = jax.block_until_ready(metrics)
        s["params"], s["opt_state"] = params, opt_state
        s["updates"] = s.iteration + 1
        s["metrics"] = {k: float(v) for k, v in metrics.items()}
        bump_flops(model_flops)
        bump_tokens(float(s["built"].tokens_per_call))
        bump_steps(1.0)

    sch.schedule(train_step, bin="EVOL", thorn="trainer")

    # --- ANALYSIS: the control plane ---------------------------------------------
    # (attached above: the ControlLoop polls every registered controller from
    # the ANALYSIS bin — AdaptCheck steering + decision, straggler reduction +
    # response — and records each decision as an ADAPT/ row)

    # --- CHECKPOINT: consume the AdaptCheck admission ------------------------------
    def adaptive_checkpoint(s: RunState) -> None:
        if manager is None or not ckpt_active:
            return
        # decision was made (with live-steered policy) at this iteration's
        # ANALYSIS poll; this routine only performs the admitted write
        decision = ckpt_control.take_decision()
        s["last_ckpt_decision"] = decision
        if decision is None or not decision.checkpoint:
            return
        with ckpt_write_scope:
            stats = manager.save(
                s["updates"],
                current_state(),
                metadata={"reason": decision.reason, **topology_meta()},
            )
        ckpt_control.observe_checkpoint(stats["blocking_seconds"], stats["nbytes"])

    sch.schedule(adaptive_checkpoint, bin="CHECKPOINT", thorn="adaptcheck")

    # --- OUTPUT ------------------------------------------------------------------------
    def output(s: RunState) -> None:
        if logger is not None:
            logger.log(s.iteration, extra=s.get("metrics"))
        if status is not None:
            status.write({"iteration": s.iteration, **(s.get("metrics") or {})})
        if settings.report_every and s.iteration % settings.report_every == 0:
            if settings.metrics_textfile:
                exporter.write_textfile(settings.metrics_textfile)
            m = s.get("metrics") or {}
            print(
                f"[train] step {s.iteration:5d} loss={m.get('loss', float('nan')):.4f} "
                f"ce={m.get('ce', float('nan')):.4f} gnorm={m.get('grad_norm', 0):.2f}"
            )

    sch.schedule(output, bin="OUTPUT", thorn="report")

    # --- SHUTDOWN --------------------------------------------------------------------
    def shutdown(s: RunState) -> None:
        if manager is not None and settings.ckpt_mode != "off":
            with ckpt_write_scope:
                manager.save(
                    s["updates"],
                    current_state(),
                    metadata={"reason": "final", **topology_meta()},
                )
            manager.wait()
            manager.close()
        s["loader"].close()
        if settings.metrics_textfile:
            exporter.write_textfile(settings.metrics_textfile)
        if monitor is not None:
            monitor.stop()

    sch.schedule(shutdown, bin="SHUTDOWN", thorn="driver")

    # --- run -----------------------------------------------------------------------------
    # the session is entered for the duration of the run so every API that
    # defaults to timer_db() (scopes opened by thorns, reports, detectors)
    # lands in the session's database
    with sess:
        sch.run(st)

    summary = {
        "iterations": st.iteration,
        "final_metrics": st.get("metrics"),
        "total_seconds": db.get("simulation/total").seconds(),
        "bin_seconds": bin_distribution(db),
        "checkpoint": controller.summary() if controller else {},
        "ckpt_fraction": (
            ckpt_write_scope.seconds() / max(db.get("simulation/total").seconds(), 1e-9)
        ),
        # the resume picture the run started from (None on a cold start):
        # which checkpoints validated, which were quarantined and why
        "resume": (
            manager.last_resume_plan.summary()
            if manager is not None and manager.last_resume_plan is not None
            else None
        ),
        "straggler_reports": len(detector.reports),
        "straggler_rows": straggler_rows(detector),
        # where the trained state lives: a mesh or pipeline that left it all
        # on one device shows here as a single entry
        "state_bytes_by_device": _bytes_by_device(
            (st["params"], st["opt_state"])
        ),
        "adapt": loop.summary(),
        "adapt_rows": adapt_rows(loop),
        # the hierarchical profile: nested inclusive/exclusive rows derived
        # from the scope stack (simulation/total → bins → routines → scopes)
        "timer_tree": tree_rows(db),
    }
    if pipelined:
        summary["pipeline"] = {
            "n_stages": settings.pipeline_stages,
            "n_layers": stage_plan.n_layers,
            "workload": cfg.name if settings.pipeline_model else "mlp",
            "n_micro": settings.pipeline_micro,
            "depths": stage_plan.depths(),
            "phase_seconds": {
                name: (
                    db.get(f"train/pipeline/{name}").seconds()
                    if db.exists(f"train/pipeline/{name}")
                    else 0.0
                )
                for name in phase_ticks(settings.pipeline_micro,
                                        settings.pipeline_stages)
            },
        }
    return summary


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", choices=list(ARCH_IDS), default="llama3.2-1b")
    ap.add_argument("--smoke", action="store_true", default=True)
    ap.add_argument("--full", dest="smoke", action="store_false")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-mode", choices=["adaptive", "fixed", "off"], default="adaptive")
    ap.add_argument("--ckpt-every", type=int, default=512)
    ap.add_argument("--ckpt-max-fraction", type=float, default=0.05)
    ap.add_argument("--ckpt-sync", action="store_true")
    ap.add_argument("--keep-n", type=int, default=3,
                    help="retention: keep the newest N checkpoints")
    ap.add_argument("--keep-every-k", type=int, default=0,
                    help="retention: additionally keep every k-th step (0 = off)")
    ap.add_argument("--save-deadline", type=float, default=None,
                    help="SIGTERM grace period (s) for the emergency save")
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--report", action="store_true", help="print the timer report")
    ap.add_argument("--monitor-port", type=int, default=None)
    ap.add_argument("--metrics-textfile", default=None,
                    help="write the Prometheus exposition here on the report "
                         "cadence (textfile-collector scrape path)")
    ap.add_argument("--pipeline-stages", type=int, default=0,
                    help="1F1B pipeline-parallel path: pod-axis size (0 = off)")
    ap.add_argument("--pipeline-layers", type=int, default=8)
    ap.add_argument("--pipeline-micro", type=int, default=4)
    ap.add_argument("--pipeline-width", type=int, default=32)
    ap.add_argument("--pipeline-model", action="store_true",
                    help="pipeline the real transformer stack (one block-pattern "
                         "period per stage slot, embed/head pinned to the end "
                         "stages) instead of the synthetic residual-MLP stack")
    args = ap.parse_args(argv)
    configure_compile_cache()

    settings = TrainSettings(
        arch=args.arch, smoke=args.smoke, steps=args.steps,
        global_batch=args.batch, seq_len=args.seq, ckpt_dir=args.ckpt_dir,
        ckpt_mode=args.ckpt_mode, ckpt_every=args.ckpt_every,
        ckpt_max_fraction=args.ckpt_max_fraction,
        ckpt_synchronous=args.ckpt_sync, peak_lr=args.lr,
        ckpt_keep_n=args.keep_n, ckpt_keep_every_k=args.keep_every_k,
        save_deadline_s=args.save_deadline,
        monitor_port=args.monitor_port,
        metrics_textfile=args.metrics_textfile,
        pipeline_stages=args.pipeline_stages,
        pipeline_layers=args.pipeline_layers,
        pipeline_micro=args.pipeline_micro,
        pipeline_width=args.pipeline_width,
        pipeline_model=args.pipeline_model,
    )
    sess = TimingSession(timer_db())
    summary = run_training(settings, session=sess)
    print(json.dumps(summary, indent=1, default=str))
    if args.report:
        # fleet-health DIST/host rows and aggregate ADAPT/ counts are already
        # in the DB; the session's control loop supplies the decision log and
        # the tree report adds the hierarchical self-vs-children view
        print(format_report(
            sess.db, channels=("walltime", "cputime", "xla_flops"),
            adapt=sess.control_loop,
        ))
        print()
        print(format_tree_report(sess.db))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
