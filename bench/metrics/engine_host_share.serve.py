"""Share of the serving window outside the program's ``serve/prefill`` and
``serve/decode`` scopes (admission, argmax syncs, splices, the generator)."""


def read(outcome):
    lay = outcome.layer
    if lay.get("kind") != "serve":
        return None
    return 100.0 * (1.0 - (lay["prefill_span_s"] + lay["decode_span_s"]) / lay["window_s"])
