"""Share of the training window outside the program's ``EVOL/trainer::train_step``
scope (data feed, control loop, reports: host work between steps)."""


def read(outcome):
    lay = outcome.layer
    if lay.get("kind") != "train":
        return None
    return 100.0 * (1.0 - lay["step_span_s"] / lay["window_s"])
