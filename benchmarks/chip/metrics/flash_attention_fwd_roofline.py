"""Kernels (kernels/ops.py): the flash-attention forward kernel's least
time over its device time, all calls of the window."""


def read(ctx):
    k = ctx.get("kernels", {}).get("flash_attention_fwd")
    if not k or not k["time_s"]:
        return None
    return 100.0 * k["least_s"] / k["time_s"]
