"""Device time a traced step of the backward: the transposed operations
(``transpose(jvp(forward))``) and the remat recompute
(``rematted_computation``), mean over the cell's chips."""
from bench import scopes


def read(ctx):
    return scopes.train_ms(ctx, "backward")
