"""Prefill FLOPs of the window (the family's ``prefill_flops``,
``bench/families``) over the seconds in ``serve/prefill`` x the chip's bf16
peak."""


def read(outcome):
    lay = outcome.layer
    if lay.get("kind") != "serve":
        return None
    if not lay["prefill_span_s"]:
        return None
    return 100.0 * lay["prefill_flops"] / (lay["prefill_span_s"] * lay["peak_flops"])
