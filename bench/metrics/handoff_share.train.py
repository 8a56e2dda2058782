"""Share of the traced part of a training window that the ops handing
activations and their gradients between pipeline stages take on a chip
(``collective-permute``; ``bench/trace.py``), averaged over the chips.  A
window with no such op reads nothing."""


def read(outcome):
    lay = outcome.layer
    if lay.get("kind") != "train":
        return None
    tr = lay.get("trace")
    if not tr or tr["window_s"] <= 0 or tr.get("handoff_s") is None:
        return None
    return 100.0 * tr["handoff_s"] / tr["window_s"]
