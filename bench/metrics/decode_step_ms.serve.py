"""Window mean of the program's ``serve/decode`` scope."""


def read(outcome):
    lay = outcome.layer
    if lay.get("kind") != "serve":
        return None
    if not lay["decode_span_n"]:
        return None
    return 1e3 * lay["decode_span_s"] / lay["decode_span_n"]
