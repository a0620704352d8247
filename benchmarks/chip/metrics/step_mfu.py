"""The whole step's share of the chip's peak FLOP/s over the traced window,
in percent: the model FLOPs of a step (``work/<kind>.py``: the forward's
matmuls times 3) times the steps, over the window and the peak. The float32
matmuls take one bfloat16 pass at the default precision, so the peak is
the bfloat16 one."""


def read(ctx):
    if not ctx["steps"]:
        return None
    flops = ctx["work"].step_flops(ctx["sizes"]) * ctx["steps"]
    window_s = ctx["trace"]["window_ns"] / 1e9
    return 100.0 * flops / window_s / ctx["peaks"]["flops_per_s"]
