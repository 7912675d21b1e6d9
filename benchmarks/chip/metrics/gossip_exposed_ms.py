"""Collective-permute time a step during which no compute operation ran
on the chip, the largest over the cell's chips."""


def read(ctx):
    if ctx["kind"] != "train" or ctx["chips"] < 2:
        return None
    t = ctx["trace"]
    if not t["collective_s"] or not any(t["collective_s"]):
        return None
    return 1e3 * max(t["collective_exposed_s"]) / ctx["traced_steps"]
