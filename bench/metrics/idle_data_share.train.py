"""Share of the traced part of a training window in which no operation ran on
the chips while the program's innermost span was a ``PRESTEP/`` routine or a
span under one: the data feed (``bench/program_spans.py``)."""

from bench import program_spans


def read(outcome):
    if outcome.layer.get("kind") != "train":
        return None
    shares = program_spans.shares(outcome)
    return None if shares is None else shares["data"]
