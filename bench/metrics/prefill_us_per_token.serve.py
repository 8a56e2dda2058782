"""Seconds in the program's ``serve/prefill`` scope over the prompt tokens it
prefilled in the window, in microseconds."""


def read(outcome):
    lay = outcome.layer
    if lay.get("kind") != "serve":
        return None
    if not lay["prefill_tokens"]:
        return None
    return 1e6 * lay["prefill_span_s"] / lay["prefill_tokens"]
