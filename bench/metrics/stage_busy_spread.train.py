"""The busiest chip's share of the traced part of a training window minus the
least busy chip's, in points (``bench/trace.py``, each device's busy time):
how unevenly the pipeline's stages are loaded.  One chip reads nothing."""


def read(outcome):
    lay = outcome.layer
    if lay.get("kind") != "train":
        return None
    tr = lay.get("trace")
    if not tr or tr["window_s"] <= 0 or len(tr["busy_s_by_device"]) < 2:
        return None
    busy = tr["busy_s_by_device"]
    return 100.0 * (max(busy) - min(busy)) / tr["window_s"]
