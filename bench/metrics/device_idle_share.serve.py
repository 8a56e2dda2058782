"""Share of the traced part of a serving window in which no operation ran on
the chip (``bench/trace.py``)."""


def read(outcome):
    lay = outcome.layer
    if lay.get("kind") != "serve":
        return None
    tr = lay.get("trace")
    if not tr or tr["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])
