"""ZeRO engine step (core/engine.py): model FLOPs of the window's steps
over the window, the chips and the chip's bf16 peak. Model FLOPs count the
forward and backward matmuls (``flops.model_flops_per_token``), not the
recomputed forward."""


def read(ctx):
    done = ctx["flops_per_token"] * ctx["tokens_per_step"] * ctx["n_steps"]
    return 100.0 * done / (ctx["window_s"] * ctx["chips"]
                           * ctx["peaks"]["bf16_flops_per_s"])
