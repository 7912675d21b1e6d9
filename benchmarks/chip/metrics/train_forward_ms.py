"""Device time a traced step of the forward (embedding, blocks, head,
loss; the operations under the ``forward`` scope that are not transposed
or recomputed), mean over the cell's chips."""
from bench import scopes


def read(ctx):
    return scopes.train_ms(ctx, "forward")
