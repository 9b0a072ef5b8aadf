"""ZeRO engine step (core/engine.py): device time of the backward, the ops
under the step's ``fwd_bwd`` scope inside JAX's ``transpose(...)`` (the
rematerialised forward included), per window step, mean over the chips."""
from benchmarks.chip import xspans


def read(ctx):
    return xspans.phase_ms_per_step(ctx, "bwd")
