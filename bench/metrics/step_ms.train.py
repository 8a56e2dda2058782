"""Window mean of the program's ``EVOL/trainer::train_step`` scope (host clock
around the step, which ends in ``block_until_ready``)."""


def read(outcome):
    lay = outcome.layer
    if lay.get("kind") != "train":
        return None
    if not lay["step_span_n"]:
        return None
    return 1e3 * lay["step_span_s"] / lay["step_span_n"]
