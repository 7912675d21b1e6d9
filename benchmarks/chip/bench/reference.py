"""Plain reference of a dense decoder, its loss, and decentralized SGD
with momentum over a Base-(k+1) graph.

Written from the published descriptions, in float32 at the ``highest``
matmul precision, with no kernel, cache or batching.  It imports nothing
of the program: its model is the configuration file's keys, and its
weights are drawn from the run seed by ``bench.weights``.

* Decoder: token embedding; per layer ``x += Attn(RMSNorm(x))`` and
  ``x += MLP(RMSNorm(x))``; a final RMSNorm and the output head (the
  embedding's transpose where tied).  RMSNorm's gain is stored as an
  offset from 1 (gain = 1 + scale), the same function as a gain vector.
  Attention is causal, with rotary embeddings (rotate-half form) on q and
  k, grouped K/V heads and optional q/k/v biases.  The MLP is
  ``down(silu(gate x) * up x)``.
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


# ---------------------------------------------------------------------------
# the decoder
# ---------------------------------------------------------------------------

def layer_names(model: dict) -> list[str]:
    """Leaf names of one decoder layer (suffixes of ``stack/blocks/0/``)."""
    names = ["ln1/scale", "attn/wq/w", "attn/wk/w", "attn/wv/w",
             "attn/wo/w", "ln2/scale", "mlp/gate/w", "mlp/up/w",
             "mlp/down/w"]
    if model["attention_bias"]:
        names += ["attn/wq/b", "attn/wk/b", "attn/wv/b"]
    return names


def layer_shapes(model: dict) -> dict:
    d, h, kv, hd = (model["hidden_size"], model["num_attention_heads"],
                    model["num_key_value_heads"], model["head_dim"])
    f = model["intermediate_size"]
    shapes = {"ln1/scale": (d,), "attn/wq/w": (d, h * hd),
              "attn/wk/w": (d, kv * hd), "attn/wv/w": (d, kv * hd),
              "attn/wo/w": (h * hd, d), "ln2/scale": (d,),
              "mlp/gate/w": (d, f), "mlp/up/w": (d, f),
              "mlp/down/w": (f, d), "attn/wq/b": (h * hd,),
              "attn/wk/b": (kv * hd,), "attn/wv/b": (kv * hd,)}
    return {n: shapes[n] for n in layer_names(model)}


def outer_shapes(model: dict) -> dict:
    d, v = model["hidden_size"], model["vocab_size"]
    shapes = {"embed/table": (v, d), "final_norm/scale": (d,)}
    if not model["tie_word_embeddings"]:
        shapes["lm_head/w"] = (d, v)
    return shapes


def param_shapes(model: dict) -> dict:
    """Every leaf of the whole model, block leaves stacked over layers."""
    shapes = dict(outer_shapes(model))
    layers = model["num_hidden_layers"]
    for n, s in layer_shapes(model).items():
        shapes[W.STACK_PREFIX + "0/" + n] = (layers,) + s
    return shapes


def served_dtype(model: dict):
    return jnp.dtype(model["torch_dtype"])


def draw_layer(key_data, model: dict, std: dict, layer):
    """Layer ``layer``'s weights in float32 (served dtype's values)."""
    dt = served_dtype(model)
    return {n: W.leaf(key_data, W.STACK_PREFIX + "0/" + n, s, std, dt,
                      layer).astype(jnp.float32)
            for n, s in layer_shapes(model).items()}


def draw_outer(key_data, model: dict, std: dict):
    dt = served_dtype(model)
    return {n: W.leaf(key_data, n, s, std, dt).astype(jnp.float32)
            for n, s in outer_shapes(model).items()}


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


def _proj(x, w, name, precision):
    y = einsum("btd,df->btf", x, w[name + "/w"], precision)
    if name + "/b" in w:
        y = y + w[name + "/b"]
    return y


def decoder_layer(x, w, model: dict, precision: str):
    """One layer over x: (B, T, D), positions 0..T-1, causal."""
    b, t, _ = x.shape
    h, kv, hd = (model["num_attention_heads"], model["num_key_value_heads"],
                 model["head_dim"])
    eps = model["rms_norm_eps"]
    pos = jnp.arange(t)
    a = rmsnorm(x, w["ln1/scale"], eps)
    q = _proj(a, w, "attn/wq", precision).reshape(b, t, h, hd)
    k = _proj(a, w, "attn/wk", precision).reshape(b, t, kv, hd)
    v = _proj(a, w, "attn/wv", precision).reshape(b, t, kv, hd)
    q = rope(q, pos, model["rope_theta"])
    k = rope(k, pos, model["rope_theta"])
    k = jnp.repeat(k, h // kv, axis=2)
    v = jnp.repeat(v, h // kv, axis=2)
    s = einsum("bqhd,bkhd->bhqk", q, k, precision) / np.sqrt(hd)
    s = jnp.where(pos[None, :] <= pos[:, None], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    o = einsum("bhqk,bkhd->bqhd", p, v, precision).reshape(b, t, h * hd)
    x = x + einsum("btf,fd->btd", o, w["attn/wo/w"], precision)
    m = rmsnorm(x, w["ln2/scale"], eps)
    g = jax.nn.silu(einsum("btd,df->btf", m, w["mlp/gate/w"], precision))
    u = einsum("btd,df->btf", m, w["mlp/up/w"], precision)
    return x + einsum("btf,fd->btd", g * u, w["mlp/down/w"], precision)


def output_weight(outer: dict):
    if "lm_head/w" in outer:
        return outer["lm_head/w"]
    return outer["embed/table"].T


def final_logits(x, outer: dict, model: dict, precision: str):
    x = rmsnorm(x, outer["final_norm/scale"], model["rms_norm_eps"])
    return einsum("...d,dv->...v", x, output_weight(outer), precision)


# ---------------------------------------------------------------------------
# training: loss, gradients, DSGD with momentum over a Base-(k+1) graph
# ---------------------------------------------------------------------------

def split_params(params: dict):
    """Whole-model leaves -> (outer leaves, per-layer list of leaves)."""
    outer = {n: a for n, a in params.items()
             if not n.startswith(W.STACK_PREFIX)}
    pre = W.STACK_PREFIX + "0/"
    blocks = {n[len(pre):]: a for n, a in params.items() if n.startswith(pre)}
    layers = next(iter(blocks.values())).shape[0]
    return outer, [{n: a[l] for n, a in blocks.items()} for l in range(layers)]


def nll_sum(params: dict, tokens, labels, model: dict, precision: str):
    """(sum of next-token NLL over labelled positions, their count).  Each
    layer is recomputed in the backward pass, which saves memory and
    leaves the arithmetic as it is."""
    outer, layers = split_params(params)
    x = outer["embed/table"][tokens]
    layer = jax.checkpoint(
        lambda x, w: decoder_layer(x, w, model, precision))
    for w in layers:
        x = layer(x, w)
    logits = final_logits(x, outer, model, precision)
    valid = labels != -100
    tgt = jnp.where(valid, labels, 0)
    lse = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, tgt[..., None], axis=-1)[..., 0]
    return jnp.sum(jnp.where(valid, lse - gold, 0.0)), jnp.sum(valid)


def node_grad(params_state: dict, tokens, labels, model: dict,
              precision: str):
    """Mean NLL over the node's labelled positions and its gradient, in
    float32, at the state's values."""
    def f(p):
        s, c = nll_sum(p, tokens, labels, model, precision)
        return s / c

    pf = {n: a.astype(jnp.float32) for n, a in params_state.items()}
    return jax.value_and_grad(f)(pf)


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
    acc = sum(w * h.astype(jnp.float32) for w, h in zip(weights, halves))
    return acc.astype(halves[0].dtype)


def train_readings(model: dict, mix: dict, std: dict, seed: int, devices,
                   *, steps: int, precision: str = "f32",
                   fault: str | None = None) -> dict:
    """Run ``steps`` steps of the configuration's training from the seed
    and return what the check compares: the mean loss of each step, each
    node's first gradient norm per leaf, and each node's change of every
    leaf after the last step.  Node ``i`` runs on ``devices[i]``.

    ``fault`` plants one of the faults the check must catch:
    ``"half_batch"`` takes the mean over the first half of each node's
    rows; ``"no_exchange"`` leaves out the gossip."""
    from . import traffic

    if fault not in (None, "half_batch", "no_exchange"):
        raise ValueError(f"unknown fault {fault!r}")
    n = mix["nodes"]
    rounds = base_graph_rounds(n, mix["k"])
    dt = served_dtype(model)
    key = W.seed_key_data(seed)
    specs = {nm: jax.ShapeDtypeStruct(s, dt)
             for nm, s in param_shapes(model).items()}

    def draw(key_data):
        return W.make_tree(key_data, specs, std)

    def grad(x, tokens, labels):
        return node_grad(x, tokens, labels, model, precision)

    def update(x, u, g):
        out = {nm: dsgdm_update(x[nm], u[nm], g[nm], beta=mix["momentum"],
                                eta=mix["eta"]) for nm in x}
        return ({nm: o[0] for nm, o in out.items()},
                {nm: o[1] for nm, o in out.items()})

    def norms(tree):
        return {nm: leaf_norm(a) for nm, a in tree.items()}

    def change(a, b):
        return {nm: leaf_norm(a[nm].astype(jnp.float32)
                              - b[nm].astype(jnp.float32)) for nm in a}

    draw_j, grad_j, update_j = jax.jit(draw), jax.jit(grad), jax.jit(update)
    norms_j, change_j, mix_j = jax.jit(norms), jax.jit(change), jax.jit(_mix)
    devs = [devices[i % len(devices)] for i in range(n)]
    x = [draw_j(jax.device_put(key, d)) for d in devs]
    u = [{nm: jnp.zeros_like(a) for nm, a in xi.items()} for xi in x]
    rows = mix["rows_per_node"] // 2 if fault == "half_batch" \
        else mix["rows_per_node"]
    losses, grad_norms = [], None
    for step in range(steps):
        batch = traffic.node_batch(step, mix, model["vocab_size"], seed)
        outs = [grad_j(x[i], jax.device_put(batch["tokens"][i, :rows], devs[i]),
                       jax.device_put(batch["labels"][i, :rows], devs[i]))
                for i in range(n)]
        if step == 0:
            grad_norms = [norms_j(g) for _, g in outs]
        losses.append(float(np.mean([float(l) for l, _ in outs])))
        halves = []
        for i in range(n):
            h, u[i] = update_j(x[i], u[i], outs[i][1])
            halves.append(h)
        del outs
        w = rounds[step % len(rounds)]
        if fault == "no_exchange" or n == 1:
            x = halves
            continue
        x = [{nm: mix_j([jax.device_put(halves[j][nm], devs[i])
                         for j in range(n) if w[i, j]],
                        [float(w[i, j]) for j in range(n) if w[i, j]])
              for nm in halves[i]} for i in range(n)]
        del halves
    del u
    start = [draw_j(jax.device_put(key, d)) for d in devs]
    changes = [change_j(x[i], start[i]) for i in range(n)]
    to_host = lambda t: {nm: float(v) for nm, v in t.items()}  # noqa: E731
    return {"losses": losses,
            "grad_norms": [to_host(t) for t in grad_norms],
            "change_norms": [to_host(t) for t in changes]}


def serve_gaps(model: dict, std: dict, seed: int, seqs, where, token_sets,
               *, precision: str = "f32"):
    """Logits of the decoder over ``seqs`` (R, T) at positions ``where``
    (R, m); returns ``(gaps, argmax)``: for each array of ``token_sets``
    (each (R, m)) how far its token's logit lies below the best logit,
    and the tokens this precision puts first.  Layer by layer, each
    layer's weights drawn when it runs."""
    key = W.seed_key_data(seed)
    layers = model["num_hidden_layers"]

    def embed_fn(key_data, seqs):
        return draw_outer(key_data, model, std)["embed/table"][seqs]

    def layer_fn(key_data, layer, x):
        w = draw_layer(key_data, model, std, layer)
        return decoder_layer(x, w, model, precision)

    def head_fn(key_data, x, where, tokens):
        outer = draw_outer(key_data, model, std)
        xs = jnp.take_along_axis(x, where[..., None], axis=1)
        logits = final_logits(xs, outer, model, precision)
        best = jnp.max(logits, axis=-1)
        gaps = [best - jnp.take_along_axis(logits, t[..., None], -1)[..., 0]
                for t in tokens]
        return gaps, jnp.argmax(logits, axis=-1)

    x = jax.jit(embed_fn)(key, jnp.asarray(seqs))
    layer_j = jax.jit(layer_fn)
    for layer in range(layers):
        x = layer_j(key, jnp.int32(layer), x)
    gaps, top = jax.jit(head_fn)(key, x, jnp.asarray(where),
                                 [jnp.asarray(t) for t in token_sets])
    return [np.asarray(g) for g in gaps], np.asarray(top)
