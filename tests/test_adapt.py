"""The repro.adapt control plane: ControlLoop dispatch + ADAPT/ recording,
AdaptCheck as a controller, straggler response (rebalance -> evict -> mesh
rebuild) over a simulated CPU fleet, and the supporting dist primitives
(LocalTransport, MicrobatchPlan, detector eviction, remove_host validation)."""

import pytest

from repro.adapt import (
    CheckpointControl,
    ControlAction,
    ControlLoop,
    Measurement,
    SimulatedFleet,
    StragglerResponse,
)
from repro.core import adapt_rows, format_adapt_report, format_report
from repro.core.adaptive import AdaptiveCheckpointPolicy
from repro.core.schedule import RunState, Scheduler
from repro.core.timers import TimerDB
from repro.dist.meshutil import local_mesh, remove_host
from repro.dist.pipeline import MicrobatchPlan
from repro.dist.stragglers import LocalTransport, StragglerDetector


# ---------------------------------------------------------------------------
# ControlLoop core
# ---------------------------------------------------------------------------


class _Bumper:
    """Minimal controller: acts whenever its polled channel has windows."""

    def __init__(self, channel="EVOL/step"):
        self.name = "bumper"
        self.channels = (channel,)
        self.seen = []

    def control(self, step, measurements):
        m = measurements[self.channels[0]]
        self.seen.append((step, m))
        if m.count == 0:
            return []
        return [
            ControlAction(
                step=step, controller=self.name, trigger=self.channels[0],
                action="bump", detail={"count": m.count},
            )
        ]


def test_control_loop_polls_channels_and_records_actions():
    db = TimerDB()
    loop = ControlLoop(db)
    ctrl = loop.register(_Bumper())

    # channel missing: measured as zero, no action
    assert loop.poll(0) == []
    assert ctrl.seen[0] == (0, Measurement(0.0, 0))

    h = db.create("EVOL/step")
    db.start(h)
    db.stop(h)
    actions = loop.poll(1)
    assert len(actions) == 1 and actions[0].action == "bump"
    assert ctrl.seen[1][1].count == 1
    # decision log + published aggregate row
    assert loop.actions == actions
    assert db.exists("ADAPT/bumper::bump")
    assert db.get("ADAPT/bumper::bump").count == 1
    assert loop.summary()["action_counts"] == {"bumper::bump": 1}


def test_control_loop_registry_rules():
    loop = ControlLoop(TimerDB())
    loop.register(_Bumper())
    with pytest.raises(ValueError):
        loop.register(_Bumper())  # duplicate name
    assert loop.controllers() == ["bumper"]
    loop.unregister("bumper")
    with pytest.raises(ValueError):
        loop.unregister("bumper")


def test_scheduler_attaches_control_loop_with_auto_timer():
    db = TimerDB()
    sch = Scheduler(db)
    loop = ControlLoop(db)
    polled = []
    loop.register(
        type(
            "Recorder",
            (),
            {
                "name": "rec",
                "channels": (),
                "control": lambda self, step, m: polled.append(step) or [],
            },
        )()
    )
    sch.attach_control_loop(loop)
    sch.run(RunState(max_iterations=3))
    assert polled == [0, 1, 2]
    # the loop poll is caliper-timed like any other routine
    assert db.exists("ANALYSIS/adapt::control_loop")
    assert db.get("ANALYSIS/adapt::control_loop").count == 3


def test_adapt_report_sections():
    db = TimerDB()
    loop = ControlLoop(db)
    loop.register(_Bumper())
    h = db.create("EVOL/step")
    db.start(h)
    db.stop(h)
    loop.poll(4)
    rows = adapt_rows(loop)
    assert rows == [
        {"step": 4, "controller": "bumper", "action": "bump",
         "trigger": "EVOL/step", "detail": {"count": 1}}
    ]
    text = format_report(db, adapt=loop)
    assert "ADAPT/bumper::bump" in text          # aggregate count row
    assert "ADAPT decisions (1)" in text         # decision-log section
    assert "bump" in format_adapt_report(loop)
    empty = format_adapt_report(ControlLoop(TimerDB()))
    assert "no adaptation decisions" in empty


# ---------------------------------------------------------------------------
# CheckpointControl (AdaptCheck on the registry)
# ---------------------------------------------------------------------------


def _fake_clock():
    state = {"t": 0.0}

    def clock():
        return state["t"]

    clock.state = state
    return clock


def test_checkpoint_control_admits_and_records():
    db = TimerDB()
    clock = _fake_clock()
    ctrl = CheckpointControl(
        AdaptiveCheckpointPolicy(mode="adaptive", max_fraction=0.5),
        ckpt_timer="CHECKPOINT/write",
        clock=clock,
    )
    ctrl.start_run(0.0)
    loop = ControlLoop(db)
    loop.register(ctrl)

    clock.state["t"] = 10.0
    actions = loop.poll(1)
    # no history, fraction 0 -> weak bound admits
    assert [a.action for a in actions] == ["checkpoint"]
    assert actions[0].detail["reason"] == "under-bound"
    decision = ctrl.take_decision()
    assert decision is not None and decision.checkpoint
    assert ctrl.take_decision() is None  # consumed

    ctrl.observe_checkpoint(seconds=9.0, nbytes=100.0)
    # now 9s of 10.1s total is checkpointing: way over the 0.5 bound
    clock.state["t"] = 10.1
    db.get(db.create("CHECKPOINT/write")).set_channel("walltime", 9.0)
    assert loop.poll(2) == []
    suppressed = ctrl.take_decision()
    assert suppressed is not None and not suppressed.checkpoint
    assert ctrl.summary()["n_suppressed"] == 1


def test_checkpoint_control_live_steering_via_registry():
    from repro.core.params import ParamRegistry

    reg = ParamRegistry()
    reg.declare("ckpt.max_fraction", 0.05, steerable=True)
    reg.declare("ckpt.max_interval_s", 1e9, steerable=True)
    clock = _fake_clock()
    ctrl = CheckpointControl(
        AdaptiveCheckpointPolicy(mode="adaptive", max_fraction=0.05,
                                 max_interval_seconds=1e9),
        clock=clock,
        registry=reg,
    )
    ctrl.start_run(0.0)
    reg.set("ckpt.max_fraction", 0.75)
    clock.state["t"] = 1.0
    ctrl.control(1, {ctrl.ckpt_timer: Measurement(0.0, 0)})
    assert ctrl.inner.policy.max_fraction == 0.75  # steered value took effect


# ---------------------------------------------------------------------------
# The acceptance scenario: simulated fleet, straggler on host k
# ---------------------------------------------------------------------------


def test_fleet_rebalance_reduces_spread_then_eviction_recovers():
    """Straggler injected on host 2: the control loop first shifts microbatches
    away from it (spread drops measurably), the host degrades further, and the
    loop evicts it and rebuilds the mesh (spread recovers to ~zero)."""
    db = TimerDB()
    fleet = SimulatedFleet(
        4, 16, db=db, window=2, threshold=1.3, check_every=1,
        confirm_after=1, evict_after=6, min_weight=0.2,
    )
    loop = ControlLoop(db)
    loop.register(fleet.controller)

    baseline_share = fleet.plan.shares()[2]
    assert baseline_share == 4

    # phase 1: mild (2x) slowdown -> rebalance suffices
    fleet.slow_host(2, 2.0)
    fleet.run_step(0)
    spread_before = fleet.spread()
    for step in range(6):
        if step:
            fleet.run_step(step)
        loop.poll(step)
    fleet.run_step(6)
    spread_after_rebalance = fleet.spread()

    rebalances = [a for a in loop.actions if a.action == "rebalance"]
    assert rebalances and rebalances[0].detail["host"] == 2
    assert fleet.plan.shares()[2] < baseline_share      # share shrank
    assert 2 in fleet.active_hosts()                    # still in the fleet
    assert spread_after_rebalance <= 0.5 * spread_before  # measurably better

    # phase 2: the host degrades badly -> weight floor -> eviction
    fleet.slow_host(2, 8.0)
    step = 7
    degraded_spread = 0.0
    while 2 in fleet.active_hosts() and step < 20:
        fleet.run_step(step)
        degraded_spread = max(degraded_spread, fleet.spread())
        loop.poll(step)
        step += 1

    evictions = [a for a in loop.actions if a.action == "evict"]
    assert len(evictions) == 1 and evictions[0].detail["host"] == 2
    assert fleet.active_hosts() == [0, 1, 3]
    assert fleet.mesh_generation == 1                   # mesh was rebuilt
    assert set(fleet.meshes) == {0, 1, 3}
    assert sum(fleet.plan.shares().values()) == 16      # work fully re-apportioned

    # recovery: homogeneous survivors -> spread collapses to the one-microbatch
    # apportionment granularity (16 over 3 hosts cannot split exactly evenly)
    fleet.run_step(step)
    granularity = max(fleet.costs.values())
    assert fleet.spread() <= granularity + 1e-9
    assert fleet.spread() < 0.1 * degraded_spread

    # every decision visible as ADAPT/ rows: weight changes, then the evict
    rows = adapt_rows(loop)
    assert rows and rows[-1]["action"] == "evict"
    assert all(r["action"] in ("rebalance", "restore") for r in rows[:-1])
    assert any(r["action"] == "rebalance" for r in rows)
    assert all(r["trigger"] == "DIST/host2::step" for r in rows)
    text = format_report(db, adapt=loop)
    assert "ADAPT/stragglers::rebalance" in text
    assert "ADAPT/stragglers::evict" in text
    # fleet-health rows tag the evicted host
    from repro.core import straggler_rows

    tagged = [r["timer"] for r in straggler_rows(fleet.detector)]
    assert any("host2::step [EVICTED]" in t for t in tagged)


def test_fleet_runs_real_pipeline_with_rebalanced_shares():
    """run_pipeline=True actually pushes each host's share through
    gpipe_forward on its local mesh, before and after a rebalance."""
    db = TimerDB()
    fleet = SimulatedFleet(
        3, 9, db=db, window=2, threshold=1.3, check_every=1,
        min_weight=0.2, run_pipeline=True,
    )
    loop = ControlLoop(db)
    loop.register(fleet.controller)
    fleet.slow_host(1, 2.0)
    for step in range(3):
        fleet.run_step(step)   # raises inside if any pipeline call breaks
        loop.poll(step)
    assert fleet.plan.shares()[1] < 3


def test_rebalanced_host_judged_on_fresh_samples_not_evicted():
    """Regression: a correctly rebalanced host must not be re-derated and
    evicted off window samples measured under its *old* (larger) share."""
    db = TimerDB()
    fleet = SimulatedFleet(
        4, 16, db=db, window=4, threshold=1.2, check_every=1,
        confirm_after=1, evict_after=4, min_weight=0.4,
    )
    loop = ControlLoop(db)
    loop.register(fleet.controller)
    fleet.slow_host(2, 2.0)  # ideal weight 0.5, comfortably above the floor
    for step in range(16):
        fleet.run_step(step)
        loop.poll(step)
    assert 2 in fleet.active_hosts()            # never evicted
    assert not [a for a in loop.actions if a.action == "evict"]
    assert abs(fleet.plan.weights[2] - 0.5) < 0.15  # settled near the ideal
    # and the fleet is balanced: host 2's step time sits at the median
    seconds = fleet.run_step(16)
    median = sorted(seconds.values())[len(seconds) // 2]
    assert seconds[2] <= 1.2 * median


def test_transient_slowdown_recovers_full_weight():
    """A derated host whose slowdown clears earns its share back (restore
    actions), so one hiccup never permanently costs fleet capacity."""
    db = TimerDB()
    fleet = SimulatedFleet(
        4, 16, db=db, window=2, threshold=1.3, check_every=1,
        confirm_after=1, evict_after=8, min_weight=0.25,
    )
    loop = ControlLoop(db)
    loop.register(fleet.controller)
    fleet.slow_host(1, 3.0)
    for step in range(5):
        fleet.run_step(step)
        loop.poll(step)
    derated = fleet.plan.weights[1]
    assert derated < 0.5 and fleet.plan.shares()[1] < 4
    fleet.slow_host(1, 1 / 3.0)  # the slowdown clears
    for step in range(5, 20):
        fleet.run_step(step)
        loop.poll(step)
    assert [a for a in loop.actions if a.action == "restore"]
    # weight climbs back until the share is restored (hysteresis stops the
    # last few percent once the host already holds its full share)
    assert fleet.plan.weights[1] > 0.8
    assert fleet.plan.shares()[1] == 4          # share back to the equal split
    assert 1 in fleet.active_hosts()


def test_granularity_blocked_straggler_hits_evict_backstop():
    """When share granularity cannot absorb a slow host (it is down to the
    1-microbatch minimum and still far off the fleet), the evict_after streak
    backstop must still fire — the fleet must not run degraded forever."""
    transport = LocalTransport()
    det = StragglerDetector(2, window=2, threshold=1.3, transport=transport,
                            publish=False)
    plan = MicrobatchPlan.equal(range(2), 4)  # tiny fleet: shares {2, 2}
    resp = StragglerResponse(det, plan, confirm_after=1, evict_after=4,
                             min_weight=0.25)
    evicted = None
    for step in range(14):
        shares = plan.shares()
        for h in plan.hosts:
            transport.publish(h, (6.0 if h == 0 else 1.0) * shares[h])
        for a in resp.control(step, {}):
            if a.action == "evict":
                evicted = a
    assert evicted is not None and evicted.detail["host"] == 0
    assert plan.hosts == [1]


def test_two_simultaneous_stragglers_both_rebalanced_same_check():
    """Acting on the first straggler shifts live shares; the second must
    still be judged against the shares its samples were measured under."""
    transport = LocalTransport()
    det = StragglerDetector(6, window=2, threshold=1.3, transport=transport,
                            publish=False)
    plan = MicrobatchPlan.equal(range(6), 24)
    resp = StragglerResponse(det, plan, confirm_after=1, evict_after=8,
                             min_weight=0.25)
    costs = {h: (3.0 if h in (1, 4) else 1.0) for h in range(6)}
    for h in plan.hosts:
        transport.publish(h, costs[h] * plan.shares()[h])
    actions = resp.control(0, {})
    assert sorted(a.detail["host"] for a in actions) == [1, 4]
    assert all(a.action == "rebalance" for a in actions)
    shares = plan.shares()
    assert shares[1] < 4 and shares[4] < 4  # both derated in one check


def test_rounding_extra_microbatch_shed_instead_of_eviction():
    """A derated host whose only residual imbalance is one rounding-parked
    microbatch sheds it (rebalance) rather than being escalated to eviction."""
    db = TimerDB()
    fleet = SimulatedFleet(
        4, 16, db=db, window=4, threshold=1.2, check_every=1,
        confirm_after=1, evict_after=4, min_weight=0.4,
    )
    loop = ControlLoop(db)
    loop.register(fleet.controller)
    fleet.slow_host(2, 2.0)
    for step in range(16):
        fleet.run_step(step)
        loop.poll(step)
    assert 2 in fleet.active_hosts()
    assert not [a for a in loop.actions if a.action == "evict"]
    # and the policy settles instead of ping-ponging shed <-> restore
    assert not [a for a in loop.actions if a.step >= 10]


def test_restore_returns_to_original_above_one_weight():
    """A host provisioned with weight > 1.0 (bigger machine) recovers to its
    ORIGINAL weight after a transient slowdown, not to the 1.0 default."""
    transport = LocalTransport()
    det = StragglerDetector(3, window=2, threshold=1.3, transport=transport,
                            publish=False)
    plan = MicrobatchPlan(n_micro=16, weights={0: 2.0, 1: 1.0, 2: 1.0})
    resp = StragglerResponse(det, plan, confirm_after=1, evict_after=8,
                             min_weight=0.25)

    def run_checks(costs, start, n):
        for step in range(start, start + n):
            shares = plan.shares()
            for h in plan.hosts:
                transport.publish(h, costs[h] * shares[h])
            resp.control(step, {})

    run_checks({0: 2.0, 1: 1.0, 2: 1.0}, 0, 2)       # host 0 transiently 2x slow
    assert plan.weights[0] < 2.0                      # derated
    assert 0 in plan.weights                          # but not evicted
    run_checks({0: 1.0, 1: 1.0, 2: 1.0}, 2, 20)      # slowdown clears
    assert plan.weights[0] > 1.5                      # climbed past the 1.0 cap
    assert plan.shares()[0] == 8                      # original double share back


def test_straggler_response_confirmation_and_hysteresis():
    """One flagged window is not acted on before confirm_after; sub-tolerance
    weight changes are suppressed."""
    transport = LocalTransport()
    det = StragglerDetector(3, window=4, threshold=1.5, transport=transport,
                            publish=False)
    plan = MicrobatchPlan.equal(range(3), 9)
    resp = StragglerResponse(det, plan, confirm_after=2, evict_after=4,
                             min_weight=0.1)
    for h in range(3):
        transport.publish(h, 4.0 if h == 1 else 1.0)
    assert resp.control(0, {}) == []          # flagged once: unconfirmed
    assert plan.shares()[1] == 3
    for h in range(3):
        transport.publish(h, 4.0 if h == 1 else 1.0)
    actions = resp.control(1, {})             # flagged twice: act
    assert [a.action for a in actions] == ["rebalance"]
    assert plan.shares()[1] < 3


# ---------------------------------------------------------------------------
# Stage-depth rebalancing (restage): the 1F1B acceptance scenario
# ---------------------------------------------------------------------------


def test_pipeline_fleet_restage_moves_stage_boundary():
    """A straggler that owns a pipeline stage is answered by moving the stage
    boundary: the ADAPT log records a ``restage``, the slow host's depth
    shrinks, its step time drops, and the restaged (uneven) boundaries really
    execute through a 1F1B pipeline_step (run_pipeline=True)."""
    db = TimerDB()
    fleet = SimulatedFleet(
        4, 8, db=db, window=2, threshold=1.3, check_every=1,
        confirm_after=1, evict_after=8, n_layers=12, run_pipeline=True,
    )
    loop = ControlLoop(db)
    loop.register(fleet.controller)
    assert fleet.stage_plan.depths() == {0: 3, 1: 3, 2: 3, 3: 3}

    fleet.slow_host(2, 2.5)
    fleet.run_step(0)
    seconds_before = fleet.last_step_seconds[2]
    for step in range(8):
        if step:
            fleet.run_step(step)
        loop.poll(step)
    fleet.run_step(8)

    restages = [a for a in loop.actions if a.action == "restage"]
    assert restages and restages[0].detail["host"] == 2
    assert restages[0].detail["stage"] == 2
    # the boundary move is preferred: no share derate before the restage
    first_action = adapt_rows(loop)[0]
    assert first_action["action"] == "restage"
    depths = fleet.stage_plan.depths()
    assert depths[2] < 3 and sum(depths.values()) == 12
    assert min(depths.values()) >= 1
    assert fleet.restages and fleet.restages[0][:2] == (2, 2)
    assert fleet.last_step_seconds[2] < seconds_before  # work really moved
    assert 2 in fleet.active_hosts()                    # moved, not evicted
    # the decision is visible as an ADAPT/ row in the timer report
    assert db.exists("ADAPT/stragglers::restage")
    assert "ADAPT/stragglers::restage" in format_report(db, adapt=loop)


def test_restage_granularity_exhausted_escalates_to_evict_backstop():
    """When every stage is already at one layer the boundary cannot move, and
    a share derate would shed no work for a stage owner (its stage runs every
    microbatch regardless) — so escalation goes straight to the evict_after
    backstop: no restage, no rebalance, eventually an eviction."""
    from repro.dist.pipeline import StagePlan

    transport = LocalTransport()
    det = StragglerDetector(3, window=2, threshold=1.3, transport=transport,
                            publish=False)
    plan = MicrobatchPlan.equal(range(3), 9)
    stage_plan = StagePlan.equal(range(3), 3)  # depths {1, 1, 1}: immovable
    resp = StragglerResponse(
        det, plan, confirm_after=1, evict_after=4, min_weight=0.25,
        stage_plan=stage_plan, stage_for_host={h: h for h in range(3)},
    )
    actions = []
    n_micro = plan.n_micro
    for step in range(8):
        depths = stage_plan.depths()
        for h in plan.hosts:
            stage = resp.stage_for_host.get(h)
            work = n_micro * depths[stage] if stage in depths else plan.shares()[h]
            transport.publish(h, (3.0 if h == 1 else 1.0) * work)
        actions += resp.control(step, {})
    kinds = [a.action for a in actions]
    assert "restage" not in kinds and "rebalance" not in kinds
    assert kinds.count("evict") == 1
    assert plan.hosts == [0, 2]
    assert set(stage_plan.weights) == {0, 2}  # evicted owner's stage dropped


def test_deliberately_deeper_stage_owner_not_misjudged():
    """Per-unit slowdown normalizes by share x stage depth: a host owning a
    deliberately deeper stage takes proportionally longer steps by design and
    must trigger no action."""
    from repro.dist.pipeline import StagePlan

    transport = LocalTransport()
    det = StragglerDetector(2, window=2, threshold=1.3, transport=transport,
                            publish=False)
    plan = MicrobatchPlan.equal(range(2), 4)           # shares {2, 2}
    stage_plan = StagePlan(n_layers=4, weights={0: 3.0, 1: 1.0})  # depths {3, 1}
    resp = StragglerResponse(
        det, plan, confirm_after=1, evict_after=8, min_weight=0.25,
        stage_plan=stage_plan, stage_for_host={0: 0, 1: 1},
    )
    for step in range(6):
        depths = stage_plan.depths()
        for h in plan.hosts:
            # identical per-unit speed; raw time scales with share x depth
            transport.publish(h, 1.0 * plan.shares()[h] * depths[h])
        assert resp.control(step, {}) == []
    assert stage_plan.depths() == {0: 3, 1: 1}
    assert plan.shares() == {0: 2, 1: 2}


def test_transient_stage_slowdown_restores_layers():
    """The stage-side restore mirror: a restaged host whose throttle clears
    earns its layers back (restore action on the stage plan), so a transient
    hiccup never permanently parks layers on the healthy stages."""
    db = TimerDB()
    fleet = SimulatedFleet(
        4, 8, db=db, window=2, threshold=1.3, check_every=1,
        confirm_after=1, evict_after=10, n_layers=12,
    )
    loop = ControlLoop(db)
    loop.register(fleet.controller)
    fleet.slow_host(2, 2.5)
    for step in range(6):
        fleet.run_step(step)
        loop.poll(step)
    assert fleet.stage_plan.depths()[2] < 3        # restaged down
    fleet.slow_host(2, 1 / 2.5)                    # the throttle clears
    for step in range(6, 20):
        fleet.run_step(step)
        loop.poll(step)
    restores = [a for a in loop.actions if a.action == "restore"]
    assert restores and restores[0].detail["host"] == 2
    assert fleet.stage_plan.depths() == {0: 3, 1: 3, 2: 3, 3: 3}  # layers back
    # and the recovered boundaries were re-packed by the fleet actuator
    assert any(r[0] == 2 and r[2][2] == 3 for r in fleet.restages)


def test_pipeline_fleet_unequal_shares_only_real_straggler_acted_on():
    """Stage owners are normalized by n_micro x depth (share-independent) and
    their microbatch weight is never derated or restored: with an unequal
    share distribution, the only host acted on is the genuinely slow one."""
    db = TimerDB()
    fleet = SimulatedFleet(
        4, 8, db=db, window=2, threshold=1.3, check_every=1,
        confirm_after=1, evict_after=8, n_layers=12,
    )
    fleet.plan.set_weight(3, 0.4)   # healthy host with a small share
    loop = ControlLoop(db)
    loop.register(fleet.controller)
    fleet.slow_host(2, 2.5)
    for step in range(8):
        fleet.run_step(step)
        loop.poll(step)
    rows = adapt_rows(loop)
    assert rows and {r["detail"]["host"] for r in rows} == {2}
    assert all(r["action"] == "restage" for r in rows)
    assert fleet.stage_plan.depths()[2] < 3


def test_restage_only_succeeds_when_stragglers_own_stage_sheds():
    """Regression: derating a stage weight can shuffle a layer between two
    *healthy* stages through largest-remainder rounding while the slow stage
    keeps its full depth — that must not count as a restage (no boundary
    churn, no streak reset); the streak keeps growing toward the evict
    backstop instead."""
    from repro.dist.pipeline import StagePlan

    transport = LocalTransport()
    det = StragglerDetector(3, window=2, threshold=1.3, transport=transport,
                            publish=False)
    plan = MicrobatchPlan.equal(range(3), 6)
    # the reviewer-found weight set: derating stage 1 moves a layer from
    # stage 0 to stage 2, never off stage 1 itself
    stage_plan = StagePlan(n_layers=11, weights={0: 0.34, 1: 2.14, 2: 2.73})
    depths_before = stage_plan.depths()
    assert depths_before == {0: 2, 1: 4, 2: 5}
    resp = StragglerResponse(
        det, plan, confirm_after=1, evict_after=10, min_weight=0.25,
        stage_plan=stage_plan, stage_for_host={0: 0, 1: 1, 2: 2},
    )
    actions = []
    for step in range(3):
        depths = stage_plan.depths()
        for h in plan.hosts:
            transport.publish(
                h, (3.0 if h == 1 else 1.0) * plan.shares()[h] * depths[h]
            )
        actions += resp.control(step, {})
    restages = [a for a in actions if a.action == "restage"]
    # every logged restage must have really shed a layer off stage 1; stage
    # owners never get a share derate, so no rebalance can appear either way
    for a in restages:
        assert a.detail["depths"][1] < depths_before[1]
    assert not [a for a in actions if a.action == "rebalance"]


def test_evicting_stage_owner_drops_its_stage_from_the_plan():
    """Regression: an evicted host's stage must leave the StagePlan (its
    layers re-apportion among survivors) — depths() must never keep
    assigning layers to a rank nobody runs."""
    from repro.dist.pipeline import StagePlan

    transport = LocalTransport()
    det = StragglerDetector(3, window=2, threshold=1.3, transport=transport,
                            publish=False)
    plan = MicrobatchPlan.equal(range(3), 6)
    stage_plan = StagePlan.equal(range(3), 3)  # depth 1 each: restage blocked
    resp = StragglerResponse(
        det, plan, confirm_after=1, evict_after=3, min_weight=0.25,
        stage_plan=stage_plan, stage_for_host={h: h for h in range(3)},
    )
    evicted = []
    for step in range(12):
        shares = plan.shares()
        for h in plan.hosts:
            transport.publish(h, (8.0 if h == 1 else 1.0) * shares[h])
        evicted += [a for a in resp.control(step, {}) if a.action == "evict"]
    assert evicted and evicted[0].detail["host"] == 1
    assert plan.hosts == [0, 2]
    assert set(stage_plan.weights) == {0, 2}          # stage 1 gone
    depths = stage_plan.depths()
    assert sum(depths.values()) == 3                  # layers re-apportioned
    assert 1 not in resp.stage_for_host


def test_stage_plan_and_host_map_must_come_together():
    from repro.dist.pipeline import StagePlan

    det = StragglerDetector(2, window=2, threshold=1.3, publish=False)
    plan = MicrobatchPlan.equal(range(2), 4)
    with pytest.raises(ValueError):
        StragglerResponse(det, plan, stage_plan=StagePlan.equal(range(2), 4))
    with pytest.raises(ValueError):
        StragglerResponse(det, plan, stage_for_host={0: 0})


# ---------------------------------------------------------------------------
# dist primitives backing the controller
# ---------------------------------------------------------------------------


def test_local_transport_gather_drains_and_drops():
    t = LocalTransport()
    t.publish(0, 1.0)
    t.publish(1, 2.0)
    t.publish(1, 3.0)
    assert t.gather() == {0: [1.0], 1: [2.0, 3.0]}
    assert t.gather() == {}
    t.drop_host(1)
    t.publish(1, 4.0)
    assert t.gather() == {}
    assert t.dropped == frozenset({1})


def test_detector_eviction_semantics():
    det = StragglerDetector(3, window=4, threshold=1.5, publish=False)
    for _ in range(4):
        for h in range(3):
            det.observe(h, 3.0 if h == 0 else 1.0)
    assert det.check(0).stragglers == [0]
    det.evict(0)
    det.observe(0, 9.0)  # late sample from the evicted host: dropped
    report = det.check(1)
    assert report.stragglers == [] and 0 not in report.host_means
    assert det.active_hosts() == [1, 2]
    assert 0 in det.host_stats()  # history survives for the report
    with pytest.raises(ValueError):
        det.evict(7)
    det.evict(1)
    with pytest.raises(ValueError):
        det.evict(2)  # cannot evict the last active host


def test_microbatch_plan_validation_and_shares():
    plan = MicrobatchPlan.equal(range(4), 16)
    assert plan.shares() == {0: 4, 1: 4, 2: 4, 3: 4}
    plan.set_weight(2, 0.5)
    shares = plan.shares()
    assert sum(shares.values()) == 16 and shares[2] < 4
    assert min(shares.values()) >= 1
    plan.evict(2)
    assert sum(plan.shares().values()) == 16
    with pytest.raises(ValueError):
        plan.set_weight(9, 1.0)
    with pytest.raises(ValueError):
        plan.set_weight(0, 0.0)
    with pytest.raises(ValueError):
        MicrobatchPlan.equal(range(5), 4)  # fewer microbatches than hosts
    solo = MicrobatchPlan.equal([0], 4)
    with pytest.raises(ValueError):
        solo.evict(0)  # cannot evict the last host


def test_remove_host_validation_on_local_mesh():
    mesh = local_mesh((1, 1))
    with pytest.raises(ValueError):
        remove_host(mesh, 0)            # size-1 axis cannot lose its slice
    with pytest.raises(ValueError):
        remove_host(mesh, 0, axis="nope")


# ---------------------------------------------------------------------------
# Real-device mesh rebuild (forced multi-device subprocess, nightly tier)
# ---------------------------------------------------------------------------

REMOVE_HOST_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.dist.meshutil import local_mesh, remove_host

mesh = local_mesh((8,), ("data",))
assert mesh.shape["data"] == 8

# evict host 3: axis shrinks, survivors keep their order
small = remove_host(mesh, 3, axis="data")
assert small.shape["data"] == 7
kept = [d.id for d in small.devices.flat]
assert kept == [0, 1, 2, 4, 5, 6, 7], kept

# the rebuilt mesh computes: a psum over the surviving axis
f = jax.shard_map(
    lambda x: jax.lax.psum(x, "data"),
    mesh=small, in_specs=P("data"), out_specs=P(),
)
out = f(jnp.ones((7, 2)))
assert out.shape == (1, 2) and float(out[0, 0]) == 7.0, (out.shape, out)

# a multi-axis mesh shrinks along the named axis only
grid = local_mesh((4, 2), ("data", "model"))
shrunk = remove_host(grid, 1, axis="data")
assert dict(shrunk.shape) == {"data": 3, "model": 2}
print("REMOVE_HOST_OK")
"""


@pytest.mark.multihost
@pytest.mark.slow
def test_remove_host_on_real_devices_subprocess():
    """Eviction rebuild on a real (forced) 8-device topology: slice removed,
    device order preserved, collectives run on the shrunk mesh."""
    import os
    import subprocess
    import sys

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(repo, "src") + os.pathsep + env.get("PYTHONPATH", "")
    proc = subprocess.run(
        [sys.executable, "-c", REMOVE_HOST_SCRIPT],
        capture_output=True, text=True, timeout=600, env=env, cwd=repo,
    )
    assert proc.returncode == 0, proc.stderr
    assert "REMOVE_HOST_OK" in proc.stdout


# ---------------------------------------------------------------------------
# checkpoint-before-evict barrier
# ---------------------------------------------------------------------------


def _drive_to_eviction(fleet, loop, host, max_steps=40):
    fleet.slow_host(host, 50.0)
    step = 0
    while host in fleet.active_hosts() and step < max_steps:
        fleet.run_step(step)
        loop.poll(step)
        step += 1
    return step


def test_checkpoint_before_evict_barrier_precedes_eviction(tmp_path):
    """An eviction gated by CheckpointControl.evict_barrier performs a durable
    save first, and the ADAPT/ log shows the checkpoint::before_evict row
    immediately before the stragglers::evict row."""
    from repro.checkpoint import CheckpointManager

    db = TimerDB()
    fleet = SimulatedFleet(
        3, 9, db=db, window=2, threshold=1.3, confirm_after=1,
        evict_after=3, min_weight=0.5,
    )
    manager = CheckpointManager(str(tmp_path), synchronous=True)
    ctrl = CheckpointControl(AdaptiveCheckpointPolicy(mode="adaptive"))
    ctrl.start_run(0.0)

    def durable_save(step):
        manager.save(step, {"w": [float(step)]})
        manager.wait()
        return 0.01

    ctrl.bind_durable_save(durable_save)
    fleet.controller.evict_barrier = ctrl.evict_barrier
    loop = ControlLoop(db)
    loop.register(fleet.controller)

    _drive_to_eviction(fleet, loop, 2)

    assert 2 not in fleet.active_hosts()
    kinds = [(a.controller, a.action) for a in loop.actions]
    evict_at = kinds.index(("stragglers", "evict"))
    assert kinds[evict_at - 1] == ("checkpoint", "before_evict")
    # the save is really on disk, durable, before the eviction committed
    assert manager.checkpoints(), "barrier save never landed"
    assert ctrl.barrier_saves == 1
    # visible in the rendered ADAPT/ report like every other adaptation
    assert "ADAPT/checkpoint::before_evict" in format_report(db, adapt=loop)
    manager.close()


def test_failed_barrier_defers_eviction_until_save_succeeds():
    """A failing durable save vetoes the eviction (the fleet must not shrink
    without a safety checkpoint); once the save path recovers, the still-
    growing streak retries and the eviction proceeds."""
    db = TimerDB()
    fleet = SimulatedFleet(
        3, 9, db=db, window=2, threshold=1.3, confirm_after=1,
        evict_after=3, min_weight=0.5,
    )
    ctrl = CheckpointControl(AdaptiveCheckpointPolicy(mode="adaptive"))
    ctrl.start_run(0.0)
    ctrl.bind_durable_save(lambda step: (_ for _ in ()).throw(OSError("disk full")))
    fleet.controller.evict_barrier = ctrl.evict_barrier
    loop = ControlLoop(db)
    loop.register(fleet.controller)

    step = _drive_to_eviction(fleet, loop, 2, max_steps=12)

    assert 2 in fleet.active_hosts(), "eviction must be deferred while saves fail"
    assert fleet.controller.deferred_evictions >= 1
    assert ctrl.barrier_failures >= 1
    assert not [a for a in loop.actions if a.action == "evict"]

    # the save path recovers -> the next flagged check evicts
    ctrl.bind_durable_save(lambda s: 0.01)
    while 2 in fleet.active_hosts() and step < 30:
        fleet.run_step(step)
        loop.poll(step)
        step += 1
    assert 2 not in fleet.active_hosts()
    kinds = [(a.controller, a.action) for a in loop.actions]
    assert ("checkpoint", "before_evict") in kinds
    assert kinds.index(("checkpoint", "before_evict")) + 1 == kinds.index(
        ("stragglers", "evict")
    )


def test_unbarriered_response_keeps_prior_semantics():
    """No evict_barrier (the default) -> eviction behaves exactly as before."""
    db = TimerDB()
    fleet = SimulatedFleet(
        3, 9, db=db, window=2, threshold=1.3, confirm_after=1,
        evict_after=3, min_weight=0.5,
    )
    loop = ControlLoop(db)
    loop.register(fleet.controller)
    _drive_to_eviction(fleet, loop, 2)
    assert 2 not in fleet.active_hosts()
    assert fleet.controller.deferred_evictions == 0
    assert not [a for a in loop.actions if a.controller == "checkpoint"]
