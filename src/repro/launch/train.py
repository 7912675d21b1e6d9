"""Decentralized training launcher.

Runs the real (allocating) distributed train loop on whatever devices
exist — production TPU pods use the same entry point with the production
mesh; on this CPU container use --devices N (fake host devices) and a
reduced arch:

    python -m repro.launch.train --arch granite-8b --reduced \
        --devices 8 --mesh-data 4 --mesh-model 2 \
        --topology base --k 1 --method dsgdm --steps 100
"""
import argparse

from repro.launch.distributed import (add_distributed_args,
                                      config_from_args, initialize)
from repro.launch.env import enable_compile_cache, set_host_device_count


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--devices", type=int, default=0,
                    help="force N host devices (CPU testing)")
    ap.add_argument("--mesh-data", type=int, default=None)
    ap.add_argument("--mesh-model", type=int, default=1)
    ap.add_argument("--production-mesh", choices=["single", "multi"],
                    default=None)
    ap.add_argument("--topology", default="base",
                    help="registered topology name, or an inline JSON "
                         "TopologySpec, e.g. '{\"name\":\"base\",\"k\":2}' "
                         "(n is filled from the mesh)")
    ap.add_argument("--k", type=int, default=1)
    ap.add_argument("--method", default="dsgdm")
    ap.add_argument("--eta", type=float, default=0.01)
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8, help="global batch")
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=0,
                    help="also checkpoint (async) every N steps")
    ap.add_argument("--flatten-gossip", action="store_true")
    ap.add_argument("--overlap", action="store_true",
                    help="overlap gossip with the method update / "
                         "backward tail (bit-exact vs sequential)")
    ap.add_argument("--compress", default=None,
                    help="gossip payload codec: identity|int8|fp8|int4|"
                         "topk, or an inline CompressionConfig JSON, "
                         "e.g. '{\"codec\":\"topk\",\"topk_frac\":0.1}' "
                         "(repro.compress; identity == uncompressed)")
    add_distributed_args(ap)
    args = ap.parse_args()

    if args.devices:
        set_host_device_count(args.devices, strict=True)
    # Multi-process bring-up (no-op for the default single-process
    # config); must precede the first jax use below.
    initialize(config_from_args(args))
    enable_compile_cache()

    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.checkpoint import AsyncCheckpointer, save_pytree
    from repro.configs import get_config
    from repro.data.synthetic import token_batches
    from repro.dist.steps import make_train_step
    from repro.launch.mesh import make_mesh, make_production_mesh
    from repro.models import model as M
    from repro.models.frontends import (stub_audio_frontend,
                                        stub_vision_frontend)

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    if args.production_mesh:
        mesh = make_production_mesh(
            multi_pod=args.production_mesh == "multi")
    else:
        nd = len(jax.devices())
        data = args.mesh_data or nd // args.mesh_model
        mesh = make_mesh((data, args.mesh_model), ("data", "model"))

    dtype = jnp.float32 if args.reduced else jnp.bfloat16
    bundle = make_train_step(cfg, mesh, topology=args.topology, k=args.k,
                             method_name=args.method, eta=args.eta,
                             param_dtype=dtype, remat=not args.reduced,
                             flatten_gossip=args.flatten_gossip,
                             overlap=args.overlap,
                             compression=args.compress)
    n = bundle.n_nodes
    print(f"topology spec: {bundle.spec.to_json()} "
          f"({bundle.n_rounds} rounds)")
    if bundle.compression is not None:
        nparams = sum(
            int(np.prod(s.shape)) for s in
            jax.tree.leaves(M.param_specs(cfg, dtype)))
        print(f"compressed gossip: {bundle.compression.to_json()} "
              f"({bundle.compression.compression_ratio(nparams):.2f}x "
              f"fewer wire bytes/message)")
    assert args.batch % n == 0
    b = args.batch // n

    key = jax.random.PRNGKey(0)
    # The bundle's own init: its state tree depends on the kernel /
    # compression configs baked in at factory time, and it places every
    # node's copy on that node's devices.
    params_n, opt = bundle.init_fn(key)

    def mk_batch(step):
        raw = token_batches(step, batch=n * b, seq=args.seq,
                            vocab=cfg.vocab_size)
        out = {k: jnp.asarray(v).reshape(n, b, -1) for k, v in raw.items()}
        kk = jax.random.fold_in(key, step)
        if cfg.frontend == "audio":
            out["frames"] = stub_audio_frontend(
                kk, n * b, cfg.d_model, dtype, frames=16
            ).reshape(n, b, 16, cfg.d_model)
        elif cfg.frontend == "vision":
            out["prefix_embeds"] = stub_vision_frontend(
                kk, n * b, cfg.d_model, dtype, patches=16
            ).reshape(n, b, 16, cfg.d_model)
        return out

    ckpt = AsyncCheckpointer(args.ckpt_dir) if args.ckpt_dir else None
    losses = []
    for step in range(args.steps):
        params_n, opt, loss = bundle.step_fn(params_n, opt, mk_batch(step),
                                             jnp.int32(step))
        losses.append(float(loss))
        if step % args.log_every == 0 or step == args.steps - 1:
            print(f"step {step:5d}  loss {losses[-1]:.4f}  "
                  f"(round {step % bundle.n_rounds}/{bundle.n_rounds})",
                  flush=True)
        if ckpt is not None and args.ckpt_every \
                and step and step % args.ckpt_every == 0:
            # Background write; the training loop keeps stepping while
            # the previous snapshot streams to disk.
            ckpt.save({"params": params_n, "opt": opt,
                       "step": jnp.int32(step)}, name="latest")
    print(f"first-10 mean {np.mean(losses[:10]):.4f}  "
          f"last-10 mean {np.mean(losses[-10:]):.4f}")
    if ckpt is not None:
        ckpt.wait()
    if args.ckpt_dir:
        avg = jax.tree.map(lambda x: x.mean(axis=0), params_n)
        print("saved:", save_pytree(avg, args.ckpt_dir))


if __name__ == "__main__":
    main()
