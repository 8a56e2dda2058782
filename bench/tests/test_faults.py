"""A run with its timed path broken underneath comes out not correct.

Each test skips the harness's look for a chip and drives the rest of a run
at a tiny size with one fault planted: a step that returns its state
unchanged, half of the batch left out, a served token altered where it is
produced.  The four-chip cell's faults, the exchange between chips among
them, are in ``test_pipeline_cell.py``.  The limits are those set at that size
(``conftest.TINY_LIMITS``).
"""

import pytest

from bench import harness


@pytest.mark.parametrize("fault", [None, "unchanged_state", "half_batch"])
def test_train_faults(fault, tiny_train, v5e):
    r = harness.run_cell(tiny_train.name, 2**31 + 21, 0.5, False, require_chip=False,
                         cell=tiny_train, peak=v5e, fault=fault)
    assert r["correct"] is (fault is None), r["checks"]


@pytest.mark.parametrize("fault", [None, "altered_token"])
def test_serve_faults(fault, tiny_serve, v5e):
    r = harness.run_cell(tiny_serve.name, 2**31 + 22, 0.5, False, require_chip=False,
                         cell=tiny_serve, peak=v5e, fault=fault)
    assert r["correct"] is (fault is None), r["checks"]
