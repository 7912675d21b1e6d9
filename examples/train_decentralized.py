"""End-to-end driver: decentralized training of a transformer LM with the
Base-(k+1) gossip schedule on a multi-device mesh (collective-permute
transport — the production path, not the simulator).

Default preset trains a ~20M-param granite-family model on 8 fake CPU
devices for 200 steps; ``--preset 100m`` uses a ~100M model (slower on
CPU; the same flags run unchanged on a real TPU mesh).

    PYTHONPATH=src python examples/train_decentralized.py \
        [--preset tiny|100m] [--steps 200] [--topology base --k 1]
"""
import argparse

from repro.launch.env import set_host_device_count


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--preset", choices=["tiny", "100m"], default="tiny")
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--topology", default="base",
                    help="registered topology name or inline JSON "
                         "TopologySpec")
    ap.add_argument("--k", type=int, default=1)
    ap.add_argument("--method", default="dsgdm")
    ap.add_argument("--devices", type=int, default=8)
    args = ap.parse_args()

    set_host_device_count(args.devices, strict=True)

    from dataclasses import replace

    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.configs import get_config
    from repro.configs.common import LayerSpec
    from repro.data.synthetic import token_batches
    from repro.dist.steps import make_train_step
    from repro.launch.mesh import make_mesh
    from repro.models import model as M
    from repro.optim.decentralized import make_method

    base = get_config("granite-8b")
    if args.preset == "tiny":
        cfg = replace(base, d_model=128, num_heads=4, num_kv_heads=2,
                      head_dim=32, d_ff=512, vocab_size=4096, num_blocks=4,
                      pattern=(LayerSpec(kind="attn", ffn="dense"),))
        batch, seq, eta = 16, 64, 0.02
    else:  # ~100M params
        cfg = replace(base, d_model=768, num_heads=12, num_kv_heads=4,
                      head_dim=64, d_ff=2048, vocab_size=16384,
                      num_blocks=10,
                      pattern=(LayerSpec(kind="attn", ffn="dense"),))
        batch, seq, eta = 8, 256, 0.01

    mesh = make_mesh((args.devices // 2, 2), ("data", "model"))
    bundle = make_train_step(cfg, mesh, topology=args.topology, k=args.k,
                             method_name=args.method, eta=eta,
                             param_dtype=jnp.float32, remat=False)
    n = bundle.n_nodes
    b = batch // n
    params = M.init(cfg, jax.random.PRNGKey(0), jnp.float32)
    pc = sum(int(np.prod(x.shape)) for x in jax.tree.leaves(params))
    print(f"arch=granite-family ({pc / 1e6:.1f}M params)  nodes={n}  "
          f"topology={bundle.spec.label} spec={bundle.spec.to_json()} "
          f"({bundle.n_rounds} rounds)  method={args.method}")
    params_n = jax.tree.map(
        lambda p: jnp.broadcast_to(p[None], (n,) + p.shape) + 0.0, params)
    opt = make_method(args.method).init(params_n)

    def mk_batch(step):
        raw = token_batches(step, batch=n * b, seq=seq,
                            vocab=cfg.vocab_size, seed=3)
        return {kk: jnp.asarray(v).reshape(n, b, seq)
                for kk, v in raw.items()}

    losses = []
    for step in range(args.steps):
        params_n, opt, loss = bundle.step_fn(
            params_n, opt, mk_batch(step), jnp.int32(step))
        losses.append(float(loss))
        if step % 20 == 0 or step == args.steps - 1:
            print(f"  step {step:4d}  loss {losses[-1]:.4f}")
    print(f"loss first-10 {np.mean(losses[:10]):.4f} -> "
          f"last-10 {np.mean(losses[-10:]):.4f}")
    assert np.mean(losses[-10:]) < np.mean(losses[:10])
    print("OK: loss decreased under decentralized gossip training.")


if __name__ == "__main__":
    main()
