"""Share of the decode window spent in the paged KV cache's ``gather``
(the benchmark's host span around the session's calls)."""


def read(ctx):
    if ctx["kind"] != "decode":
        return None
    s = ctx["host_span_s"].get("bench.kv_gather")
    return None if s is None else 100.0 * s / ctx["window_s"]
