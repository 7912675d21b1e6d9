"""The plain reference's shared part: its precisions, RMSNorm and RoPE,
the loss, and decentralized SGD with momentum over a Base-(k+1) graph.

The architecture is a module of its own, ``archs/<name>.py``, which the
configuration file names under ``"reference"`` (``bench.spec``): its
leaves, embedding, layers and head.  Written from the published
descriptions, in float32 at the ``highest`` matmul precision, with no
kernel, cache or batching.  It imports nothing of the program: its model
is the configuration file's keys, and its weights are drawn from the run
seed by ``bench.weights``.

* DSGD with momentum (paper Eq. (1)): ``u' = beta u + g``, ``x' = W(r)
  (x - eta u')`` with round ``r = step mod rounds``.  The state is kept in
  the dtype the configuration states, so it is rounded there after each
  update; so is the half step ``x - eta u'``, a tree of the parameters'
  dtype in the method's definition, before it is mixed in float32.
* Base-(k+1) graph for ``n = (k+1)^m`` nodes: in round ``r`` every node
  averages with the ``k+1`` nodes whose base-(k+1) digits differ from its
  own at digit ``r`` only; after ``m`` rounds all nodes hold the exact
  mean.

``precision="fp8"`` is the control: every matmul and attention product
takes float8 e4m3 operands with one scale per tensor, and the backward
pass float8 e5m2 cotangents, as fp8 training does.

``Reference`` builds its jitted programs once, so that every seed after
the first, and every call, reuses what they compiled.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from . import weights as W

HIGHEST = jax.lax.Precision.HIGHEST
E4M3_MAX = 448.0
E5M2_MAX = 57344.0
PRECISIONS = ("f32", "fp8")
FAULTS = (None, "half_batch", "no_exchange")


# ---------------------------------------------------------------------------
# matmuls at the reference's precision, or the control's
# ---------------------------------------------------------------------------

def _quant(x, dtype, top):
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / top
    return (x / scale).astype(dtype).astype(jnp.float32) * scale


def _q4(x):
    return _quant(x, jnp.float8_e4m3fn, E4M3_MAX)


def _q5(x):
    return _quant(x, jnp.float8_e5m2, E5M2_MAX)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _fp8_einsum(spec, a, b):
    return jnp.einsum(spec, _q4(a), _q4(b), precision=HIGHEST)


def _fp8_fwd(spec, a, b):
    qa, qb = _q4(a), _q4(b)
    return jnp.einsum(spec, qa, qb, precision=HIGHEST), (qa, qb)


def _fp8_bwd(spec, res, g):
    qa, qb = res
    _, vjp = jax.vjp(
        lambda x, y: jnp.einsum(spec, x, y, precision=HIGHEST), qa, qb)
    return vjp(_q5(g))


_fp8_einsum.defvjp(_fp8_fwd, _fp8_bwd)


def einsum(spec, a, b, precision):
    if precision == "f32":
        return jnp.einsum(spec, a, b, precision=HIGHEST)
    if precision == "fp8":
        return _fp8_einsum(spec, a, b)
    raise ValueError(f"unknown precision {precision!r}")


def rmsnorm(x, scale, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * (1.0 + scale)


def rope(x, positions, theta):
    """x: (B, T, H, hd); positions: (T,)."""
    half = x.shape[-1] // 2
    freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = positions[:, None].astype(jnp.float32) * freq
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


# ---------------------------------------------------------------------------
# the model's leaves, by the architecture's names
# ---------------------------------------------------------------------------

def served_dtype(model: dict):
    return jnp.dtype(model["torch_dtype"])


def param_shapes(arch, model: dict) -> dict:
    """Every leaf of the whole model as the program names it, stacked
    leaves with their stacked axis."""
    shapes = dict(arch.outer_shapes(model))
    for i in range(model["num_hidden_layers"]):
        for leaf, index, shape in arch.layer_leaves(model, i).values():
            if index is None:
                shapes[leaf] = shape
            else:
                depth = shapes.get(leaf, (0,))[0]
                shapes[leaf] = (max(depth, index + 1),) + tuple(shape)
    return shapes


def draw_outer(arch, key_data, model: dict, std: dict) -> dict:
    dt = served_dtype(model)
    return {n: W.leaf(key_data, n, s, std, dt).astype(jnp.float32)
            for n, s in arch.outer_shapes(model).items()}


def layer_tags(arch, model: dict, i: int):
    """Layer ``i``'s leaves' name tags and stack indices: what picks its
    weights in a program that every layer of its kind shares."""
    leaves = arch.layer_leaves(model, i)
    return ({n: np.uint32(W.name_tag(leaf)) for n, (leaf, _, _)
             in leaves.items()},
            {n: np.int32(idx or 0) for n, (_, idx, _) in leaves.items()})


def draw_layer(arch, key_data, model: dict, std: dict, i: int, tags,
               index) -> dict:
    """The weights of a layer of layer ``i``'s kind in float32 (the
    served dtype's values), picked by ``layer_tags``, which may be
    traced."""
    dt = served_dtype(model)
    return {n: W.draw(key_data, tags[n], W.kind_of(leaf), shape, std, dt,
                      None if idx is None else index[n]).astype(jnp.float32)
            for n, (leaf, idx, shape) in arch.layer_leaves(model, i).items()}


def split_params(arch, params: dict, model: dict):
    """Whole-model leaves -> (outer leaves, per-layer list of leaves)."""
    outer = {n: params[n] for n in arch.outer_shapes(model)}
    layers = [{n: params[leaf] if index is None else params[leaf][index]
               for n, (leaf, index, _) in arch.layer_leaves(model, i).items()}
              for i in range(model["num_hidden_layers"])]
    return outer, layers


def nll_sum(arch, params: dict, tokens, labels, model: dict, precision: str):
    """(sum of next-token NLL over labelled positions, their count).  Each
    layer is recomputed in the backward pass, which saves memory and
    leaves the arithmetic as it is."""
    outer, layers = split_params(arch, params, model)
    x = arch.embed(tokens, outer, model)
    for i, w in enumerate(layers):
        x = jax.checkpoint(functools.partial(
            arch.layer, model=model, i=i, precision=precision))(x, w)
    logits = arch.head(x, outer, model, precision)
    valid = labels != -100
    tgt = jnp.where(valid, labels, 0)
    lse = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, tgt[..., None], axis=-1)[..., 0]
    return jnp.sum(jnp.where(valid, lse - gold, 0.0)), jnp.sum(valid)


# ---------------------------------------------------------------------------
# decentralized SGD with momentum over a Base-(k+1) graph
# ---------------------------------------------------------------------------

def base_graph_rounds(n: int, k: int) -> list[np.ndarray]:
    """Mixing matrices of the Base-(k+1) graph for n = (k+1)^m nodes."""
    base = k + 1
    m = 0
    while base ** m < n:
        m += 1
    if base ** m != n:
        raise ValueError(f"the reference covers n a power of {base}; "
                         f"got n={n}")
    if n == 1:
        return [np.ones((1, 1))]
    digits = np.array([[(i // base ** r) % base for r in range(m)]
                       for i in range(n)])
    rounds = []
    for r in range(m):
        rest = np.delete(digits, r, axis=1)
        same = (rest[:, None, :] == rest[None, :, :]).all(-1)
        rounds.append(same / base)
    return rounds


def dsgdm_update(x, u, g, *, beta: float, eta: float):
    """Momentum and step of one leaf; state rounded to its dtype."""
    uf = beta * u.astype(jnp.float32) + g
    half = (x.astype(jnp.float32) - eta * uf).astype(x.dtype)
    return half, uf.astype(u.dtype)


def leaf_norm(a):
    return jnp.sqrt(jnp.sum(jnp.square(a.astype(jnp.float32))))


def _mix(halves, weights):
    """One node's round: the weighted sum of the trees it averages, in
    float32, rounded to the state's dtype."""
    def leaf(*hs):
        acc = sum(w * h.astype(jnp.float32) for w, h in zip(weights, hs))
        return acc.astype(hs[0].dtype)
    return jax.tree.map(leaf, *halves)


# ---------------------------------------------------------------------------
# the reference of one configuration, its programs compiled once
# ---------------------------------------------------------------------------

class Reference:
    """The plain reference of one configuration under one traffic mix at
    one precision.  Its jitted programs are made here, once: each
    compiles on its first call, and every later seed and call reuses it.
    Layers whose leaves have the same names and shapes share one serving
    program, traced with the first of them as the layer's index, so an
    architecture's ``layer`` must compute the same function of their
    weights."""

    def __init__(self, arch, model: dict, mix: dict, precision: str = "f32"):
        if precision not in PRECISIONS:
            raise ValueError(f"unknown precision {precision!r}")
        self.arch, self.model, self.mix = arch, model, mix
        self.precision, self.std = precision, mix["weights"]
        self.dtype = served_dtype(model)
        specs = {nm: jax.ShapeDtypeStruct(s, self.dtype)
                 for nm, s in param_shapes(arch, model).items()}
        self._draw = jax.jit(lambda key: W.make_tree(key, specs, self.std))
        self._grad = jax.jit(self._node_grad)
        self._update = jax.jit(self._dsgdm)
        self._norms = jax.jit(lambda t: {nm: leaf_norm(a)
                                         for nm, a in t.items()})
        self._change = jax.jit(lambda a, b: {
            nm: leaf_norm(a[nm].astype(jnp.float32)
                          - b[nm].astype(jnp.float32)) for nm in a})
        self._mix = jax.jit(_mix)
        self._embed = jax.jit(self._serve_embed)
        self._head = jax.jit(self._serve_head)
        self._layers = {}

    # -- training ------------------------------------------------------------

    def _node_grad(self, params_state, tokens, labels):
        """Mean NLL over the node's labelled positions and its gradient,
        in float32, at the state's values."""
        def f(p):
            s, c = nll_sum(self.arch, p, tokens, labels, self.model,
                           self.precision)
            return s / c

        pf = {n: a.astype(jnp.float32) for n, a in params_state.items()}
        return jax.value_and_grad(f)(pf)

    def _dsgdm(self, x, u, g):
        out = {nm: dsgdm_update(x[nm], u[nm], g[nm],
                                beta=self.mix["momentum"], eta=self.mix["eta"])
               for nm in x}
        return ({nm: o[0] for nm, o in out.items()},
                {nm: o[1] for nm, o in out.items()})

    def train_readings(self, seed: int, devices, *, steps: int,
                       fault: str | None = None) -> dict:
        """Run ``steps`` steps of the configuration's training from the
        seed and return what the check compares: the mean loss of each
        step, each node's first gradient norm per leaf, and each node's
        change of every leaf after the last step.  Node ``i`` runs on
        ``devices[i]``.

        ``fault`` plants one of the faults the check must catch:
        ``"half_batch"`` takes the mean over the first half of each
        node's rows; ``"no_exchange"`` leaves out the gossip."""
        from . import traffic

        if fault not in FAULTS:
            raise ValueError(f"unknown fault {fault!r}")
        mix = self.mix
        n = mix["nodes"]
        rounds = base_graph_rounds(n, mix["k"])
        key = W.seed_key_data(seed)
        devs = [devices[i % len(devices)] for i in range(n)]
        x = [self._draw(jax.device_put(key, d)) for d in devs]
        u = [{nm: jnp.zeros_like(a) for nm, a in xi.items()} for xi in x]
        rows = mix["rows_per_node"] // 2 if fault == "half_batch" \
            else mix["rows_per_node"]
        losses, grad_norms = [], None
        for step in range(steps):
            batch = traffic.node_batch(step, mix, self.model["vocab_size"],
                                       seed)
            outs = [self._grad(
                x[i], jax.device_put(batch["tokens"][i, :rows], devs[i]),
                jax.device_put(batch["labels"][i, :rows], devs[i]))
                for i in range(n)]
            if step == 0:
                grad_norms = [self._norms(g) for _, g in outs]
            losses.append(float(np.mean([float(l) for l, _ in outs])))
            halves = []
            for i in range(n):
                h, u[i] = self._update(x[i], u[i], outs[i][1])
                halves.append(h)
            del outs
            w = rounds[step % len(rounds)]
            if fault == "no_exchange" or n == 1:
                x = halves
                continue
            x = [self._mix([jax.device_put(halves[j], devs[i])
                            for j in range(n) if w[i, j]],
                           [float(w[i, j]) for j in range(n) if w[i, j]])
                 for i in range(n)]
            del halves
        del u
        start = [self._draw(jax.device_put(key, d)) for d in devs]
        changes = [self._change(x[i], start[i]) for i in range(n)]
        to_host = lambda t: {nm: float(v) for nm, v in t.items()}  # noqa: E731
        return {"losses": losses,
                "grad_norms": [to_host(t) for t in grad_norms],
                "change_norms": [to_host(t) for t in changes]}

    # -- serving -------------------------------------------------------------

    def _serve_embed(self, key_data, seqs):
        outer = draw_outer(self.arch, key_data, self.model, self.std)
        return self.arch.embed(seqs, outer, self.model)

    def _serve_layer(self, i, key_data, tags, index, x):
        """A layer of layer ``i``'s kind over x, its weights picked by
        ``tags`` and ``index``."""
        w = draw_layer(self.arch, key_data, self.model, self.std, i, tags,
                       index)
        return self.arch.layer(x, w, self.model, i, self.precision)

    def _serve_head(self, key_data, x, where, tokens):
        outer = draw_outer(self.arch, key_data, self.model, self.std)
        xs = jnp.take_along_axis(x, where[..., None], axis=1)
        logits = self.arch.head(xs, outer, self.model, self.precision)
        best = jnp.max(logits, axis=-1)
        gaps = [best - jnp.take_along_axis(logits, t[..., None], -1)[..., 0]
                for t in tokens]
        return gaps, jnp.argmax(logits, axis=-1)

    def _layer_program(self, i: int):
        """The jitted program of layer ``i``'s kind and its arguments."""
        leaves = self.arch.layer_leaves(self.model, i)
        kind = tuple(sorted((n, tuple(shape), idx is None)
                            for n, (_, idx, shape) in leaves.items()))
        if kind not in self._layers:
            self._layers[kind] = jax.jit(
                functools.partial(self._serve_layer, i))
        return (self._layers[kind],) + layer_tags(self.arch, self.model, i)

    def serve_gaps(self, seed: int, seqs, where, token_sets):
        """Logits of the model over ``seqs`` (R, T) at positions ``where``
        (R, m); returns ``(gaps, argmax)``: for each array of
        ``token_sets`` (each (R, m)) how far its token's logit lies below
        the best logit, and the tokens this precision puts first.  Layer
        by layer, each layer's weights drawn when it runs."""
        key = W.seed_key_data(seed)
        x = self._embed(key, jnp.asarray(seqs))
        for i in range(self.model["num_hidden_layers"]):
            program, tags, index = self._layer_program(i)
            x = program(key, tags, index, x)
        gaps, top = self._head(key, x, jnp.asarray(where),
                               [jnp.asarray(t) for t in token_sets])
        return [np.asarray(g) for g in gaps], np.asarray(top)
