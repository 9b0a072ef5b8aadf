"""Device (TPU): the share of the traced window in which no operation ran,
mean over the chips."""


def read(ctx):
    dev = ctx.get("devices")
    if not dev:
        return None
    busy = [c["busy_ns"] for c in dev["chips"].values()]
    return 100.0 * (1.0 - sum(busy) / len(busy) / dev["window_ns"])
