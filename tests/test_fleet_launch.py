"""Tier-1 fleet smoke: REAL subprocess ranks over the rendezvous store.

Two short end-to-end runs through ``repro.fleet.launch.run_fleet``:

* the elasticity smoke — 2 provisioned ranks, a mid-run join, then a SIGKILL;
  asserts the join earned share, the kill was *detected* (heartbeat expiry →
  barrier-gated leave), every transition is an ``ADAPT/fleet::*`` row, and the
  joins/leaves/epoch deltas are visible on the wire between the first and last
  scraped Prometheus pages;
* the payback smoke — ``horizon_steps=0`` (no future to amortize against), so
  the same join is deferred every poll with the measured re-shard cost in the
  action detail and the epoch never moves.

These spawn real processes and sleep on real heartbeats: budget a few seconds
each, which is the price of exercising the actual multi-process path in tier-1.
"""

import numpy as np
import pytest

from repro.fleet.launch import FleetSettings, run_fleet
from repro.monitor.promparse import parse_exposition
from repro.soak.invariants import SnapshotRecord, check_snapshots


def _wire_value(snapshot, name):
    return parse_exposition(snapshot["exposition"]).value(name)


def test_two_rank_join_and_kill_smoke(tmp_path):
    settings = FleetSettings(
        hosts=2,
        steps=30,
        step_floor_s=0.02,
        poll_interval_s=0.1,
        liveness_timeout_s=0.8,
        snapshot_every=5,
        rendezvous=str(tmp_path / "rdzv"),
        join_at=[(4, 2)],
        kill_at=[(15, 0)],
    )
    summary = run_fleet(settings)

    # membership arithmetic: one join, one kill-triggered leave, each an epoch
    assert summary["joins_total"] == 1
    assert summary["leaves_total"] == 1
    assert summary["epoch"] == 3
    assert summary["hosts"] == [1, 2]
    # every survivor holds share; the whole microbatch budget stays assigned
    assert sorted(summary["shares"]) == [1, 2]
    assert sum(summary["shares"].values()) == settings.n_micro

    # the kill went through the checkpoint-before-evict barrier
    counts = summary["action_counts"]
    assert counts.get("fleet::join") == 1
    assert counts.get("fleet::leave") == 1
    assert counts.get("checkpoint::before_evict", 0) >= 1
    assert summary["barrier_saves"] >= 1

    # ranks: the joiner and the survivor drained cleanly; the killed rank
    # never wrote a final record (SIGKILL leaves no goodbye)
    finals = summary["finals"]
    assert finals["1"]["status"] == "done" and finals["1"]["steps"] > 0
    assert finals["2"]["status"] == "done" and finals["2"]["steps"] > 0
    assert "0" not in finals

    # wire visibility: the joins/leaves/epoch transitions are Prometheus
    # deltas between the first and last scraped pages
    first, last = summary["snapshots"][0], summary["snapshots"][-1]
    assert _wire_value(first, "repro_fleet_joins_total") == 0.0
    assert _wire_value(last, "repro_fleet_joins_total") == 1.0
    assert _wire_value(first, "repro_fleet_leaves_total") == 0.0
    assert _wire_value(last, "repro_fleet_leaves_total") == 1.0
    assert _wire_value(first, "repro_fleet_membership_epoch") == 1.0
    assert _wire_value(last, "repro_fleet_membership_epoch") == 3.0
    assert _wire_value(last, "repro_fleet_hosts") == 2.0

    # and the full soak invariant set holds over the scraped sequence
    records = [
        SnapshotRecord(
            index=i, step=s["step"], source="render",
            actions=dict(s["actions"]),
            exposition=parse_exposition(s["exposition"]),
        )
        for i, s in enumerate(summary["snapshots"])
    ]
    assert check_snapshots(records) == []

    # the workers converged on the shared problem (they did real work)
    losses = [f["loss"] for f in finals.values()]
    assert all(np.isfinite(losses))


def test_zero_horizon_defers_join_with_measured_cost(tmp_path):
    settings = FleetSettings(
        hosts=2,
        # the joiner needs ~0.5 s of interpreter start-up before it can ask
        # to join; 16 polls of 0.1 s after the spawn leave it room on a busy host
        steps=20,
        step_floor_s=0.02,
        poll_interval_s=0.1,
        liveness_timeout_s=2.0,
        horizon_steps=0,  # no payback horizon: every optional move defers
        snapshot_every=4,
        rendezvous=str(tmp_path / "rdzv"),
        join_at=[(3, 2)],
    )
    summary = run_fleet(settings)

    # the join request was gated every poll, never admitted
    assert summary["joins_total"] == 0
    assert summary["epoch"] == 1
    assert summary["hosts"] == [0, 1]
    assert summary["reshard_defers"]["join"] >= 1
    assert summary["action_counts"].get("fleet::defer_reshard", 0) >= 1

    # the defer detail carries the measured (startup save+restore) cost
    defer_rows = [a for a in summary["actions"] if "defer_reshard" in a]
    assert defer_rows
    assert summary["reshard_cost_s"] > 0.0
    assert "reshard_cost_s=" in defer_rows[-1]
    assert "reason=join" in defer_rows[-1]

    # defers are wire-visible too
    assert _wire_value(
        summary["snapshots"][-1], "repro_fleet_reshard_defers_total"
    ) >= 1.0

    # the gated joiner eventually gives up via the shutdown key (status
    # admit_timeout would need a longer run; here it just must not wedge the
    # run) — both provisioned ranks drained cleanly
    finals = summary["finals"]
    assert finals["0"]["status"] == "done"
    assert finals["1"]["status"] == "done"


@pytest.mark.slow
def test_seeded_drill_invariants_hold():
    """One full nightly-style drill seed: seeded rank-fault matrix against
    real processes, checked by the drill's own invariant set."""
    from repro.fleet.drill import run_drill

    result = run_drill(0, hosts=3, steps=40)
    assert result["failures"] == []
