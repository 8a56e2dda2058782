"""Share of the traced part of a training window in which no operation ran on
the chips while the program's innermost span was the train step's own
``EVOL/trainer::train_step``, its ``dispatch`` (enqueuing the compiled step) or
its ``fetch`` (the metrics' copy to the host): host work inside the step
(``bench/program_spans.py``)."""

from bench import program_spans


def read(outcome):
    if outcome.layer.get("kind") != "train":
        return None
    shares = program_spans.shares(outcome)
    return None if shares is None else shares["step_host"]
