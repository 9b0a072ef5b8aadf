"""Trainer loop (train/trainer.py): device idle time inside the loop's own
host spans (``train.data``, ``train.shard``, ``train.dispatch``,
``train.fetch``, ``train.log``, ``train.ckpt``) per window step, mean over
the chips: what the loop's host work costs the device. Idle time inside
``train.wait`` is the step's own and is left out."""
from benchmarks.chip import xspans


def read(ctx):
    rec = xspans.window_trace(ctx)
    if rec is None:
        return None
    loop = [(s, e) for name, s, e in xspans.host_spans(rec, xspans.LOOP)
            if name != "train.wait"]
    if not loop:
        return None
    return xspans.idle_within(rec, loop) / ctx["n_steps"] * 1e-6
