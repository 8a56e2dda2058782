"""Share of the traced part of a training window in which no operation ran on
the chips while the program waited for the step in
``EVOL/trainer::train_step/wait`` (``block_until_ready``): gaps on the device
side of the step that host code does not cause (``bench/program_spans.py``)."""

from bench import program_spans


def read(outcome):
    if outcome.layer.get("kind") != "train":
        return None
    shares = program_spans.shares(outcome)
    return None if shares is None else shares["step_device"]
