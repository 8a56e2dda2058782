"""Decode FLOPs of the window over the seconds in ``serve/decode`` x the chip's
bf16 peak: the whole decode step's share of the peak, beside its roofline."""


def read(outcome):
    lay = outcome.layer
    if lay.get("kind") != "serve":
        return None
    if not lay["account_ok"] or not lay["decode_span_s"]:
        return None
    return 100.0 * lay["decode_flops"] / (lay["decode_span_s"] * lay["peak_flops"])
