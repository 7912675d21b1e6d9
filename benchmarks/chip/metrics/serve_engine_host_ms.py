"""The scheduler's own host time: mean, over the decode calls of the
run's untraced window, of the next decode's dispatch less this one's
output being ready, less the prefill calls in between."""


def read(ctx):
    if ctx["kind"] != "serve":
        return None
    host, ready, inner = [], None, 0.0
    for kind, t0, t1, *_ in ctx["calls"]:
        if kind == "prefill":
            inner += t1 - t0
            continue
        if ready is not None:
            host.append(t0 - ready - inner)
        ready, inner = t1, 0.0
    return 1e3 * sum(host) / len(host) if host else None
