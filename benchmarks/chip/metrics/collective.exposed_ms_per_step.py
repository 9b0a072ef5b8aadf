"""Collectives (core/collectives.py): the part of the collective time
during which no other operation ran on the chip, per step, mean over the
chips."""


def read(ctx):
    dev = ctx.get("devices")
    if not dev or not any(c["collective_ns"] for c in dev["chips"].values()):
        return None
    per_chip = [c["exposed_ns"] for c in dev["chips"].values()]
    return sum(per_chip) / len(per_chip) / ctx["n_steps"] * 1e-6
