"""95th percentile, over the requests arriving in the window, of the wait from
scheduled arrival to admission (the program's ``RequestResult.admitted_at``)."""


def read(outcome):
    lay = outcome.layer
    if lay.get("kind") != "serve":
        return None
    return lay["queue_p95_ms"]
