"""ZeRO engine step (core/engine.py): device time of the optimizer, the ops
under the step's ``gnorm_clip`` and ``update`` scopes (AdamW and the update
all-gather), per window step, mean over the chips."""
from benchmarks.chip import xspans


def read(ctx):
    return xspans.phase_ms_per_step(ctx, "update")
