"""The forward embedding kernel's share of its roofline, in percent.

The least time of one step's lookups is the larger of their bytes over the
peak HBM bandwidth and their adds over the peak FLOP/s, per pooled store
(``workcore.emb_fwd_bytes``: ids, each gathered row once, the pooled
output), summed over the stores and the traced steps. The kernel's time is
the sum of the device time of its calls in the trace: the Pallas calls
(custom calls to ``tpu_custom_call``), which on the dense update's path are
the forward lookups alone, one per pooled store and step. A trace with no
such call gives nothing.
"""

KERNEL = "tpu_custom_call"


def read(ctx):
    op_ns = ctx["trace"]["op_ns"]
    kernel_ns = sum(v for k, v in op_ns.items() if KERNEL in k)
    if not kernel_ns or not ctx["steps"]:
        return None
    import workcore
    sizes, peaks = ctx["sizes"], ctx["peaks"]
    least_s = 0.0
    for dim in ctx["work"].pool_dims(sizes):
        least_s += max(workcore.emb_fwd_bytes(sizes, dim)
                       / peaks["hbm_bytes_per_s"],
                       workcore.emb_fwd_flops(sizes, dim)
                       / peaks["flops_per_s"])
    return 100.0 * least_s * ctx["steps"] / (kernel_ns / 1e9)
