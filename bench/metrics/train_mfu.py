"""Model FLOPs of the window's steps (the family's ``train_step_flops`` in
``bench/families``: 6 x layer and head params x tokens plus causal attention,
no recomputation) over window x chips x the chip's bf16 peak."""


def read(outcome):
    lay = outcome.layer
    if lay.get("kind") != "train":
        return None
    return 100.0 * lay["model_flops"] / (lay["window_s"] * lay["chips"] * lay["peak_flops"])
