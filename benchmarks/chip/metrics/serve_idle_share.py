"""Share of the traced window in which no operation ran on the device."""


def read(ctx):
    if ctx["kind"] != "serve":
        return None
    t = ctx["trace"]
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])
