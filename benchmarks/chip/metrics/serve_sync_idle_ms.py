"""Device-idle time inside the engine's ``serve.sync`` spans (the host
fetching a call's output), over their count, in the traced part."""
from bench import scopes


def read(ctx):
    if ctx["kind"] != "serve":
        return None
    s = scopes.serve(ctx["trace_path"], ctx["devices"][0])
    if not s["syncs"]:
        return None
    return 1e3 * s["idle_in_sync_s"] / s["syncs"]
