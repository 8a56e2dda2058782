"""The decode steps' least possible time, each max(FLOPs / peak, bytes / HBM
bandwidth) with the bytes the step needs (weights, the KV of each active
row's real positions; the family's ``decode_step_cost``), over the seconds in
``serve/decode``.  Left out where the driver's account of the rows disagrees
with a finished request."""


def read(outcome):
    lay = outcome.layer
    if lay.get("kind") != "serve":
        return None
    if not lay["account_ok"] or not lay["decode_span_s"]:
        return None
    return 100.0 * lay["decode_ideal_s"] / lay["decode_span_s"]
