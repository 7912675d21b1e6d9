"""Device time a traced step of the gossip round (the ``gossip`` scope:
casts, the combine kernel, collective-permutes and the waits on them),
mean over the cell's chips."""
from bench import scopes


def read(ctx):
    return scopes.train_ms(ctx, "gossip")
