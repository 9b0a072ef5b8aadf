"""Kernels (kernels/ops.py): the fused INT8-dequant matmul's least time
over its device time, all calls of the window (``xtrace.kernel_summary``)."""


def read(ctx):
    k = ctx.get("kernels", {}).get("dequant_matmul")
    if not k or not k["time_s"]:
        return None
    return 100.0 * k["least_s"] / k["time_s"]
