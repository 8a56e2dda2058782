"""Share of the traced part of a training window in which no operation ran on
the chips while the program was in neither the data feed nor the train step:
other routines (the benchmark's own among them), the bins between routines,
or no program span (``bench/program_spans.py``)."""

from bench import program_spans


def read(outcome):
    if outcome.layer.get("kind") != "train":
        return None
    shares = program_spans.shares(outcome)
    return None if shares is None else shares["loop"]
