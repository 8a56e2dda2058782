"""The four-chip pipeline cell on four virtual CPU devices, at a tiny size.

The reference split over the devices agrees with the unsplit one; a run of the
cell through the program's 1F1B path is correct against it; the control (the
reference in fp8 in the program's place) and each fault the cell can have,
planted underneath the harness, read not correct: a step that returns its
state unchanged, half of the batch left out, and a stage that receives zeros
in place of one microbatch's activations (the exchange between chips).  The
cases run in one process of their own (``four_devices.py``), since the device
count is fixed when JAX starts.
"""

import json
import os
import subprocess
import sys

import pytest

from conftest import ROOT, TINY_LIMITS

FAULTS = ["unchanged_state", "half_batch", "handoff"]


@pytest.fixture(scope="module")
def cases():
    names = ["split", "run:none"] + [f"run:{f}" for f in FAULTS]
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    p = subprocess.run([sys.executable, os.path.join(ROOT, "bench", "tests", "four_devices.py"),
                        *names], cwd=ROOT, env=env, capture_output=True, text=True, timeout=1500)
    assert p.returncode == 0, p.stderr[-4000:]
    out = {}
    for line in p.stdout.splitlines():
        if line.startswith("{"):
            r = json.loads(line)
            out[r["case"]] = r
    assert sorted(out) == sorted(names)
    return out


def test_split_reference_agrees_with_the_unsplit(cases):
    """Over four devices and over one, every number the check compares is the
    unsplit reference's to float32 rounding (and the rare element whose
    gradient lands on the other side of a bfloat16 rounding step)."""
    r = cases["split"]
    assert r["layers"] == 8
    assert r["gap_4"] <= 2e-5 and r["gap_1"] <= 2e-5, r


def test_split_control_agrees_with_the_unsplit(cases):
    """The fp8 control through the split path is the unsplit reference's
    fp8 control: the same control as the one-chip cell's limits were set
    against before the split."""
    r = cases["split"]
    assert r["gap_4_fp8"] <= 2e-5 and r["gap_1_fp8"] <= 2e-5, r


def test_a_sound_run_is_correct(cases):
    r = cases["run:none"]
    assert r["chips"] == 4
    assert r["correct"] is True, r["checks"]


def test_the_control_is_not_correct(cases):
    r = cases["run:none"]
    assert r["limits"] == TINY_LIMITS["train.glm4-9b.pp4"]
    assert r["control_correct"] is False, r["control"]


@pytest.mark.parametrize("fault", FAULTS)
def test_a_fault_is_not_correct(cases, fault):
    r = cases[f"run:{fault}"]
    assert r["chips"] == 4
    assert r["correct"] is False, r["checks"]
