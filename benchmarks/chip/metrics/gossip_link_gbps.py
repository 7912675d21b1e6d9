"""What a chip sends through the gossip's collective-permutes (bytes of
their results) over the time they and their transfers take (the union
of their intervals), mean over the cell's chips."""
from bench import scopes


def read(ctx):
    if ctx["kind"] != "train" or ctx["chips"] < 2 or not ctx["traced_steps"]:
        return None
    link = scopes.train(ctx["trace_path"], ctx["devices"],
                        ctx["traced_steps"])["link"]
    rates = [c["gbps"] for c in link if c["gbps"]]
    return sum(rates) / len(rates) if rates else None
