"""Mean host-clock time of a prefill call in the run's untraced window,
from the call to its first token being ready."""


def read(ctx):
    if ctx["kind"] != "serve":
        return None
    times = [c[2] - c[1] for c in ctx["calls"] if c[0] == "prefill"]
    if not times:
        return None
    return 1e3 * sum(times) / len(times)
