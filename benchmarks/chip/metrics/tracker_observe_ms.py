"""Host milliseconds per step in ``HotTableTracker.observe`` (the
``bench/observe`` span around it), over the traced window."""


def read(ctx):
    spans = ctx["trace"]["span_ns"].get("observe")
    if not spans or not ctx["steps"]:
        return None
    return sum(spans) / 1e6 / ctx["steps"]
