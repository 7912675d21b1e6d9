"""Device time a traced step of the method's update (the ``update``
scope less the gossip inside it), mean over the cell's chips."""
from bench import scopes


def read(ctx):
    return scopes.train_ms(ctx, "update")
