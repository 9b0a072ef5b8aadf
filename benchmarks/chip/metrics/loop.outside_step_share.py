"""Trainer loop (train/trainer.py): the share of the window outside the
program's own step timings (``TrainLog.step_times``, each ending in
``block_until_ready``): making and placing the batch, fetching the
metrics, logging."""


def read(ctx):
    return 100.0 * (1.0 - sum(ctx["step_times"]) / ctx["window_s"])
