"""Fleet-layer unit tests: the rendezvous store's atomicity and log-offset
contracts, the epoch-fenced cross-process transport, elastic membership and
its controller (joins, duplicate joins, barrier-gated leaves, heartbeat expiry
racing a publish), the payback gates, membership-aware stage derivation, the
straggler-response elastic hooks, and the wire view (`/fleet` endpoint,
exporter families, soak epoch-monotonicity invariant)."""

import json
import os
import urllib.error
import urllib.request

import pytest

from repro.adapt.controller import ControlAction, ControlLoop
from repro.adapt.stragglers import StragglerResponse
from repro.core.timers import TimerDB
from repro.dist.pipeline import MicrobatchPlan, StagePlan
from repro.dist.stragglers import StragglerDetector, StragglerReport
from repro.fleet import (
    FleetController,
    FleetTransport,
    Membership,
    PaybackPolicy,
    ReshardCost,
)
from repro.fleet.store import FileStore
from repro.fleet.topology import data_parallel_rank, stage_for_host
from repro.monitor.export import MetricsExporter
from repro.monitor.promparse import parse_exposition
from repro.monitor.server import MonitorServer
from repro.soak.invariants import SnapshotRecord, check_snapshots


# --- FileStore ----------------------------------------------------------------

def test_store_put_get_delete_roundtrip(tmp_path):
    store = FileStore(str(tmp_path))
    store.put("membership", {"epoch": 3})
    assert store.get("membership") == {"epoch": 3}
    store.put("membership", {"epoch": 4})  # atomic replace
    assert store.get("membership")["epoch"] == 4
    store.delete("membership")
    assert store.get("membership", default="gone") == "gone"
    store.delete("membership")  # idempotent


def test_store_rejects_traversal_keys(tmp_path):
    store = FileStore(str(tmp_path))
    for bad in ("../escape", "a//b", "/abs", "a/../b", ""):
        with pytest.raises(ValueError):
            store.put(bad, {})


def test_store_scan_one_level(tmp_path):
    store = FileStore(str(tmp_path))
    store.put("join/3", {"host": 3})
    store.put("join/7", {"host": 7})
    store.put("beat/3", {"t": 1.0})
    scanned = store.scan("join")
    assert sorted(scanned) == ["join/3", "join/7"]
    assert scanned["join/7"] == {"host": 7}
    assert store.scan("nonexistent") == {}


def test_store_log_offsets_consume_only_complete_lines(tmp_path):
    store = FileStore(str(tmp_path))
    store.append("samples/0", {"s": 1.0})
    store.append("samples/0", {"s": 2.0})
    records, offset = store.read_log("samples/0")
    assert [r["s"] for r in records] == [1.0, 2.0]
    # a torn (in-flight) append must stay in the file for the next read
    path = os.path.join(str(tmp_path), "samples", "0.jsonl")
    with open(path, "ab") as f:
        f.write(b'{"s": 3.0')  # no newline: mid-write
    records, offset2 = store.read_log("samples/0", offset)
    assert records == [] and offset2 == offset
    with open(path, "ab") as f:
        f.write(b"}\n")
    records, offset3 = store.read_log("samples/0", offset2)
    assert [r["s"] for r in records] == [3.0] and offset3 > offset2
    # undecodable complete lines are skipped, offset still advances
    with open(path, "ab") as f:
        f.write(b"not json\n")
    records, offset4 = store.read_log("samples/0", offset3)
    assert records == [] and offset4 > offset3


def test_store_logs_listing(tmp_path):
    store = FileStore(str(tmp_path))
    store.append("samples/0", {"s": 1.0})
    store.append("samples/2", {"s": 1.0})
    assert store.logs("samples") == ["samples/0", "samples/2"]
    assert store.logs("empty") == []


# --- FleetTransport -----------------------------------------------------------

def _members(epoch, joined):
    return lambda: (epoch, dict(joined))


def test_transport_publish_gather_across_instances(tmp_path):
    store = FileStore(str(tmp_path))
    worker = FleetTransport(store, host=0)
    worker.epoch = 1
    controller = FleetTransport(store, members_fn=_members(1, {0: 1}))
    worker.publish(0, 0.05)
    worker.publish(0, 0.06)
    assert controller.gather() == {0: [0.05, 0.06]}
    assert controller.gather() == {}  # offsets advanced: nothing new
    worker.publish(0, 0.07)
    assert controller.gather() == {0: [0.07]}
    assert controller.stale_rejected == 0


def test_transport_epoch_fence_rejects_stale_and_foreign(tmp_path):
    store = FileStore(str(tmp_path))
    worker = FleetTransport(store, host=1)
    controller = FleetTransport(store, members_fn=_members(3, {1: 3}))
    worker.epoch = 2  # stamped before host 1's admission epoch
    worker.publish(1, 0.05)
    worker.epoch = 3
    worker.publish(1, 0.06)
    stranger = FleetTransport(store, host=9)
    stranger.epoch = 3
    stranger.publish(9, 0.04)  # not in membership at all
    assert controller.gather() == {1: [0.06]}
    assert controller.stale_rejected == 2


def test_transport_drop_host_fences_local(tmp_path):
    store = FileStore(str(tmp_path))
    worker = FleetTransport(store, host=0)
    worker.epoch = 1
    controller = FleetTransport(store, members_fn=_members(1, {0: 1}))
    controller.drop_host(0)
    worker.publish(0, 0.05)
    assert controller.gather() == {}
    assert controller.dropped == frozenset({0})
    assert controller.stale_rejected == 1


def test_transport_heartbeat_writes_beat_key(tmp_path):
    store = FileStore(str(tmp_path))
    worker = FleetTransport(store, host=4)
    worker.heartbeat()
    beat = store.get("beat/4")
    assert beat["pid"] == os.getpid() and beat["t"] > 0


# --- topology -----------------------------------------------------------------

def test_stage_for_host_contiguous_blocks():
    assert stage_for_host([0, 1, 2, 3], 2) == {0: 0, 1: 0, 2: 1, 3: 1}
    # sparse, unsorted ids: ownership follows sorted order
    assert stage_for_host([7, 2, 5], 3) == {2: 0, 5: 1, 7: 2}
    # fewer hosts than stages: each host owns its block's first stage
    assert stage_for_host([0, 1], 4) == {0: 0, 1: 2}
    # the single-host launcher case that replaced the {0: 0} stub
    assert stage_for_host([0], 4) == {0: 0}
    assert stage_for_host([], 2) == {}
    assert stage_for_host([0, 1], 0) == {}


def test_stage_for_host_covers_all_stages_when_enough_hosts():
    for n_hosts in range(3, 9):
        for n_stages in range(1, n_hosts + 1):
            owned = set(stage_for_host(range(n_hosts), n_stages).values())
            assert owned == set(range(n_stages))


def test_data_parallel_rank_dense_and_stable():
    assert data_parallel_rank([7, 2, 5], 5) == 1
    assert data_parallel_rank([7, 2, 5], 7) == 2
    with pytest.raises(ValueError):
        data_parallel_rank([0, 1], 9)


# --- payback ------------------------------------------------------------------

def test_reshard_cost_starts_at_fallback_and_observe_moves_it():
    cost = ReshardCost()
    assert cost.save_s == pytest.approx(0.006)
    assert cost.restore_s == pytest.approx(0.003)
    assert cost.total() == pytest.approx(0.009)
    cost.observe(save_s=0.106, restore_s=0.203)  # ewma 0.5
    assert cost.save_s == pytest.approx(0.056)
    assert cost.restore_s == pytest.approx(0.103)


def test_reshard_cost_observe_ewma():
    cost = ReshardCost(save_s=1.0, restore_s=1.0, ewma=0.5)
    cost.observe(save_s=3.0)
    assert cost.save_s == pytest.approx(2.0)
    cost.observe(restore_s=0.0)  # non-positive observations are ignored
    assert cost.restore_s == pytest.approx(1.0)
    assert cost.total() == pytest.approx(3.0)


def _report(step, host_means, median, stragglers):
    return StragglerReport(
        step=step, host_means=host_means, median=median,
        stragglers=stragglers, threshold=2.0,
    )


def test_evict_gate_passes_when_win_covers_cost():
    policy = PaybackPolicy(
        ReshardCost(save_s=0.1, restore_s=0.1, rebuild_s=0.0),
        horizon_steps=10,
    )
    # host 2 wastes 0.08 s/step past the median: 0.8 s over the horizon > 0.2
    report = _report(5, {0: 0.02, 1: 0.02, 2: 0.10}, 0.02, [2])
    assert policy.evict_gate(5, 2, report, 5.0) is None
    assert policy.defers["evict"] == 0


def test_evict_gate_defers_and_logs_the_numbers():
    policy = PaybackPolicy(
        ReshardCost(save_s=1.0, restore_s=1.0), horizon_steps=10
    )
    report = _report(5, {0: 0.02, 1: 0.02, 2: 0.10}, 0.02, [2])
    action = policy.evict_gate(5, 2, report, 5.0)
    assert action is not None and action.action == "defer_reshard"
    assert action.controller == "fleet"
    assert action.detail["reason"] == "evict" and action.detail["host"] == 2
    assert action.detail["projected_win_s"] == pytest.approx(0.8)
    assert action.detail["reshard_cost_s"] == pytest.approx(2.0)
    assert policy.defers["evict"] == 1


def test_zero_horizon_defers_every_optional_move():
    policy = PaybackPolicy(ReshardCost(), horizon_steps=0, min_hosts=1)
    report = _report(1, {0: 0.01, 1: 5.0}, 0.01, [1])
    assert policy.evict_gate(1, 1, report, 500.0) is not None
    assert policy.join_gate(1, 9, n_active=2, mean_step_s=10.0) is not None
    assert policy.defers == {"evict": 1, "join": 1}
    with pytest.raises(ValueError):
        PaybackPolicy(ReshardCost(), horizon_steps=-1)


def test_join_gate_bypasses_below_min_hosts():
    policy = PaybackPolicy(ReshardCost(save_s=9.0), horizon_steps=0, min_hosts=2)
    # fleet below provisioned size: rebuilding, never speculative
    assert policy.join_gate(1, 5, n_active=1, mean_step_s=0.0) is None
    # at provisioned size the gate applies (horizon 0 always defers)
    assert policy.join_gate(1, 5, n_active=2, mean_step_s=1.0) is not None


# --- Membership ---------------------------------------------------------------

def _membership(tmp_path, hosts=(0, 1), n_micro=8, **kw):
    store = FileStore(str(tmp_path))
    plan = MicrobatchPlan.equal(hosts, n_micro)
    return store, plan, Membership(store, plan, **kw)


def test_membership_publishes_record_on_init(tmp_path):
    store, plan, membership = _membership(tmp_path, n_stages=2)
    record = store.get("membership")
    assert record["epoch"] == 1 and record["n_micro"] == 8
    assert sorted(record["hosts"]) == ["0", "1"]
    assert record["hosts"]["0"]["share"] == 4
    assert record["hosts"]["1"]["stage"] == 1
    assert record["hosts"]["0"]["joined_epoch"] == 1


def test_membership_admit_grows_plan_in_place_and_fences(tmp_path):
    store, plan, membership = _membership(tmp_path)
    assert membership.admit(2) is True
    assert membership.epoch == 2 and membership.joined_epoch[2] == 2
    assert sorted(plan.weights) == [0, 1, 2]  # the shared object grew
    assert store.get("membership")["hosts"]["2"]["joined_epoch"] == 2
    # duplicate admit: idempotent, no epoch bump, no re-apportionment
    assert membership.admit(2) is False
    assert membership.epoch == 2


def test_membership_remove_bumps_epoch_and_clears_keys(tmp_path):
    store, plan, membership = _membership(tmp_path)
    store.put("beat/1", {"t": 1.0})
    store.put("join/1", {"host": 1})
    membership.remove(1)
    assert membership.hosts == [0] and membership.epoch == 2
    assert 1 not in membership.joined_epoch
    assert store.get("beat/1") is None and store.get("join/1") is None
    assert store.get("membership")["epoch"] == 2


def test_membership_expiry_from_fake_clock(tmp_path):
    now = [100.0]
    store, plan, membership = _membership(
        tmp_path, liveness_timeout=2.0, clock=lambda: now[0]
    )
    store.put("beat/0", {"t": 100.0})
    store.put("beat/1", {"t": 100.0})
    now[0] = 101.0
    assert membership.expired() == []
    now[0] = 103.5
    store.put("beat/0", {"t": 103.0})  # host 0 kept beating
    assert membership.expired() == [1]
    ages = membership.beat_ages()
    assert ages[0] == pytest.approx(0.5) and ages[1] == pytest.approx(3.5)


# --- FleetController ----------------------------------------------------------

def _fleet(tmp_path, hosts=(0, 1, 2), *, payback=None, barrier=None,
           liveness=2.0, clock=None, n_micro=9):
    now = [100.0]
    clock = clock or (lambda: now[0])
    store = FileStore(str(tmp_path))
    plan = MicrobatchPlan.equal(hosts, n_micro)
    membership = Membership(
        store, plan, liveness_timeout=liveness, clock=clock
    )
    transport = FleetTransport(store, members_fn=membership.members_fn)
    detector = StragglerDetector(
        len(hosts), window=4, threshold=2.0, publish=False, transport=transport
    )
    response = StragglerResponse(detector, plan, evict_after=3)
    controller = FleetController(
        membership, transport, response,
        payback=payback, evict_barrier=barrier, clock=clock,
    )
    for h in hosts:
        store.put(f"beat/{h}", {"t": clock()})
    return store, membership, transport, detector, response, controller, now


def test_controller_join_admits_and_registers(tmp_path):
    store, membership, transport, detector, response, fleet, now = _fleet(tmp_path)
    store.put("join/3", {"host": 3})
    actions = fleet.control(1, {})
    assert [a.action for a in actions] == ["join"]
    assert actions[0].detail["host"] == 3 and actions[0].detail["epoch"] == 2
    assert fleet.joins_total == 1
    assert membership.hosts == [0, 1, 2, 3]
    assert detector.n_hosts == 4  # response grew the detector in lockstep
    assert store.get("join/3") is None  # request consumed
    assert "DIST/host3::step" in response.channels


def test_controller_duplicate_join_is_idempotent(tmp_path):
    store, membership, transport, detector, response, fleet, now = _fleet(tmp_path)
    store.put("join/1", {"host": 1})  # already a member
    actions = fleet.control(1, {})
    assert actions == [] and fleet.joins_total == 0
    assert membership.epoch == 1  # no bump
    assert store.get("join/1") is None  # acked (consumed) anyway


def test_controller_join_deferred_by_payback_stays_pending(tmp_path):
    policy = PaybackPolicy(ReshardCost(save_s=9.0), horizon_steps=0, min_hosts=1)
    store, membership, transport, detector, response, fleet, now = _fleet(
        tmp_path, payback=policy
    )
    store.put("join/5", {"host": 5})
    actions = fleet.control(1, {})
    assert [a.action for a in actions] == ["defer_reshard"]
    assert membership.hosts == [0, 1, 2] and fleet.joins_total == 0
    assert store.get("join/5") is not None  # retried next poll
    # a later poll with the gate satisfied admits it
    fleet.payback = None
    actions = fleet.control(2, {})
    assert [a.action for a in actions] == ["join"]


def test_controller_leave_runs_barrier_then_removes(tmp_path):
    saves = []

    def barrier(step, report):
        saves.append(step)
        return ControlAction(step=step, controller="checkpoint",
                             trigger="ckpt", action="before_evict", detail={})

    store, membership, transport, detector, response, fleet, now = _fleet(
        tmp_path, barrier=barrier
    )
    now[0] = 110.0  # every beat is stale; only host 2's refreshed
    store.put("beat/1", {"t": 110.0})
    store.put("beat/2", {"t": 110.0})
    actions = fleet.control(7, {})
    assert [a.action for a in actions] == ["before_evict", "leave"]
    assert actions[1].detail == {
        "host": 0, "reason": "heartbeat_expired", "epoch": 2,
        "survivors": [1, 2],
    }
    assert saves == [7] and fleet.leaves_total == 1
    assert membership.hosts == [1, 2] and detector.n_hosts == 3
    assert 0 in detector.evicted


def test_controller_leave_deferred_by_barrier_veto(tmp_path):
    store, membership, transport, detector, response, fleet, now = _fleet(
        tmp_path, barrier=lambda step, report: None
    )
    now[0] = 110.0  # no member refreshed: every beat is past the timeout
    actions = fleet.control(3, {})
    assert actions == [] and fleet.leaves_total == 0
    assert fleet.deferred_leaves >= 1  # vetoed, retried next poll
    assert membership.hosts == [0, 1, 2]  # nothing removed yet
    # join processed during the in-flight (deferred) evict barrier: admitted
    store.put("join/7", {"host": 7})
    actions = fleet.control(4, {})
    assert "join" in [a.action for a in actions]
    assert 7 in membership.hosts


def test_controller_never_fences_out_last_host(tmp_path):
    store, membership, transport, detector, response, fleet, now = _fleet(
        tmp_path, hosts=(0,), n_micro=4
    )
    now[0] = 200.0  # far past every timeout
    actions = fleet.control(1, {})
    assert actions == [] and membership.hosts == [0]


def test_heartbeat_expiry_racing_a_publish(tmp_path):
    """A rank that publishes samples and then dies: the leave fences it, and
    samples it wrote before (or after) the removal never reach the means."""
    store, membership, transport, detector, response, fleet, now = _fleet(tmp_path)
    worker = FleetTransport(store, host=0)
    worker.epoch = 1
    worker.publish(0, 0.05)  # in flight before the expiry is noticed
    now[0] = 110.0
    store.put("beat/1", {"t": 110.0})
    store.put("beat/2", {"t": 110.0})
    actions = fleet.control(9, {})
    assert [a.action for a in actions] == ["leave"]
    worker.publish(0, 0.06)  # zombie publish after removal
    detector.observe(1, 0.01)
    detector.observe(2, 0.01)
    report = detector.check(9)
    assert 0 not in report.host_means
    assert transport.stale_rejected >= 1  # the fence did the rejection
    assert membership.hosts == [1, 2]


def test_stale_epoch_rejected_after_rejoin_of_same_id_is_impossible(tmp_path):
    """Evicted ids never return (detector contract) — a stale incarnation's
    samples are rejected by the admission-epoch fence."""
    store, membership, transport, detector, response, fleet, now = _fleet(tmp_path)
    now[0] = 110.0
    store.put("beat/1", {"t": 110.0})
    store.put("beat/2", {"t": 110.0})
    fleet.control(1, {})  # evicts host 0 at epoch 2
    with pytest.raises(ValueError):
        detector.add_host(0)  # the id is burned
    zombie = FleetTransport(store, host=0)
    zombie.epoch = 1  # its pre-eviction view
    zombie.publish(0, 0.5)
    assert transport.gather() == {}
    assert transport.stale_rejected == 1


def test_controller_on_the_control_loop_records_adapt_rows(tmp_path):
    db = TimerDB()
    store, membership, transport, detector, response, fleet, now = _fleet(tmp_path)
    loop = ControlLoop(db)
    loop.register(fleet)
    store.put("join/3", {"host": 3})
    loop.poll(1)
    counts = loop.summary()["action_counts"]
    assert counts.get("fleet::join") == 1
    assert db.get("ADAPT/fleet::join").count == 1


# --- StragglerResponse elastic hooks ------------------------------------------

def _response(hosts=(0, 1, 2), n_micro=9, **kw):
    plan = MicrobatchPlan.equal(hosts, n_micro)
    detector = StragglerDetector(len(hosts), window=4, publish=False)
    return plan, detector, StragglerResponse(detector, plan, **kw)


def test_register_host_requires_plan_membership():
    plan, detector, response = _response()
    with pytest.raises(ValueError):
        response.register_host(3)  # not in the plan: grow the plan first
    grown = plan.retarget([0, 1, 2, 3])
    plan.weights.clear()
    plan.weights.update(grown.weights)
    response.register_host(3)
    assert detector.n_hosts == 4
    assert "DIST/host3::step" in response.channels


def test_register_host_with_stage_updates_stage_map():
    stage_plan = StagePlan.equal(range(2), 4)
    plan, detector, response = _response(
        stage_plan=stage_plan, stage_for_host={0: 0, 1: 1, 2: 1}
    )
    grown = plan.retarget([0, 1, 2, 3])
    plan.weights.clear()
    plan.weights.update(grown.weights)
    response.register_host(3, stage=1)
    assert response.stage_for_host[3] == 1


def test_remove_host_shrinks_plan_detector_and_stages():
    stage_plan = StagePlan.equal(range(2), 4)
    plan, detector, response = _response(
        stage_plan=stage_plan, stage_for_host={0: 0, 1: 1, 2: 1}
    )
    response.remove_host(2)
    assert sorted(plan.weights) == [0, 1]
    assert 2 in detector.evicted
    assert 2 not in response.stage_for_host
    assert sorted(stage_plan.weights) == [0, 1]  # stage 1 still owned by host 1
    response.remove_host(1)  # last owner of stage 1: the stage is orphaned
    assert sorted(stage_plan.weights) == [0]


def test_reshard_gate_defers_eviction_and_keeps_streak():
    deferred = []

    def gate(step, host, report, slowdown):
        deferred.append(host)
        return ControlAction(step=step, controller="fleet",
                             trigger=f"DIST/host{host}::step",
                             action="defer_reshard", detail={"host": host})

    plan, detector, response = _response(
        check_every=1, confirm_after=1, evict_after=2, min_weight=0.5,
        reshard_gate=gate,
    )
    for step in range(1, 8):
        for h in (0, 1):
            detector.observe(h, 0.01)
        detector.observe(2, 0.2)
        response.control(step, {})
    assert response.deferred_reshards >= 1
    assert deferred and set(deferred) == {2}
    assert 2 in plan.weights  # never actually evicted
    assert 2 not in detector.evicted


# --- wire views ---------------------------------------------------------------

def _wired(tmp_path):
    store, membership, transport, detector, response, fleet, now = _fleet(tmp_path)
    store.put("join/3", {"host": 3})
    fleet.control(1, {})
    return fleet


def test_status_payload_shape(tmp_path):
    fleet = _wired(tmp_path)
    payload = fleet.status_payload()
    assert payload["epoch"] == 2 and payload["joins_total"] == 1
    assert sorted(payload["hosts"]) == ["0", "1", "2", "3"]
    entry = payload["hosts"]["3"]
    assert entry["joined_epoch"] == 2 and entry["share"] >= 1
    assert payload["reshard_defers_total"] == 0
    assert payload["stale_samples_rejected"] == 0


def test_exporter_fleet_families_render_and_parse(tmp_path):
    fleet = _wired(tmp_path)
    exporter = MetricsExporter(TimerDB(), fleet_fn=fleet.status_payload)
    page = parse_exposition(exporter.render())
    assert page.value("repro_fleet_hosts") == 4.0
    assert page.value("repro_fleet_membership_epoch") == 2.0
    assert page.value("repro_fleet_joins_total") == 1.0
    assert page.value("repro_fleet_leaves_total") == 0.0
    assert page.value("repro_fleet_reshard_defers_total") == 0.0
    assert page.value("repro_fleet_stale_samples_total") == 0.0
    shares = page.series("repro_fleet_host_share")
    assert len(shares) == 4 and all(v >= 1.0 for v in shares.values())


def test_monitor_fleet_endpoint(tmp_path):
    fleet = _wired(tmp_path)
    server = MonitorServer(port=0, db=TimerDB(), fleet_fn=fleet.status_payload)
    server.start()
    try:
        url = f"http://127.0.0.1:{server.port}/fleet"
        with urllib.request.urlopen(url, timeout=5) as resp:
            payload = json.loads(resp.read())
        assert payload["epoch"] == 2 and "3" in payload["hosts"]
    finally:
        server.stop()


def test_monitor_fleet_endpoint_404_when_unwired():
    server = MonitorServer(port=0, db=TimerDB())
    server.start()
    try:
        url = f"http://127.0.0.1:{server.port}/fleet"
        with pytest.raises(urllib.error.HTTPError) as err:
            urllib.request.urlopen(url, timeout=5)
        assert err.value.code == 404
    finally:
        server.stop()


# --- soak invariant: membership epoch monotonicity -----------------------------

def _epoch_page(mono, epoch):
    return parse_exposition(
        "# TYPE repro_scrape_monotonic_seconds gauge\n"
        f"repro_scrape_monotonic_seconds {mono}\n"
        "# TYPE repro_fleet_membership_epoch gauge\n"
        f"repro_fleet_membership_epoch {epoch}\n"
    )


def _snaps(epochs):
    return [
        SnapshotRecord(index=i, step=i, source="render",
                       exposition=_epoch_page(float(i + 1), e))
        for i, e in enumerate(epochs)
    ]


def test_soak_epoch_monotonicity_passes_on_climb():
    failures = check_snapshots(_snaps([1, 1, 2, 4, 4]))
    assert not any("epoch" in f for f in failures)


def test_soak_epoch_monotonicity_trips_on_regression():
    failures = check_snapshots(_snaps([1, 3, 2]))
    assert any("membership epoch regressed 3 -> 2" in f for f in failures)


def test_soak_epoch_check_skips_pages_without_the_family():
    bare = parse_exposition(
        "# TYPE repro_scrape_monotonic_seconds gauge\n"
        "repro_scrape_monotonic_seconds 9.0\n"
    )
    snaps = _snaps([1, 5])
    snaps.append(SnapshotRecord(index=2, step=2, source="render", exposition=bare))
    failures = check_snapshots(snaps)
    assert not any("epoch" in f for f in failures)
