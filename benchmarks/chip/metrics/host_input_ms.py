"""Host milliseconds per step spent making the step's input: the loader,
the id remap and the copy to the device (the ``bench/loader``,
``bench/remap`` and ``bench/h2d`` spans), over the traced window."""

SPANS = ("loader", "remap", "h2d")


def read(ctx):
    span_ns = ctx["trace"]["span_ns"]
    if not ctx["steps"] or not any(span_ns.get(s) for s in SPANS):
        return None
    return sum(sum(span_ns.get(s, ())) for s in SPANS) / 1e6 / ctx["steps"]
