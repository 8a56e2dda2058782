"""Share of the traced part of a training window in which no operation ran on
the chips (``bench/trace.py``, averaged over the chips)."""


def read(outcome):
    lay = outcome.layer
    if lay.get("kind") != "train":
        return None
    tr = lay.get("trace")
    if not tr or tr["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])
