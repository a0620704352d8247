"""Samples per second of the checkpoint cell's window, as ``samples_per_s``
counts them in the other cells: every step's samples over the whole window.
Here its runs spread too widely to hold a bound (see ``PERF.md``), so it is
read per layer, beside ``ckpt_stall_ms``."""


def read(ctx):
    if not ctx["save_stall_s"]:
        return None
    return ctx["steps"] * ctx["batch"] / ctx["window_s"]
