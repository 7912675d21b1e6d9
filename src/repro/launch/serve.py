"""Serving launcher: fixed-batch engine or continuous-batching frontend.

Fixed-batch mode (default) is a thin CLI over ``repro.serve.make_engine``:
prefill a batch of prompts, then generate with the compiled decode
engine — the whole generation phase is ONE executable call (scan over
token positions, on-device sampling), not a per-token dispatch loop.

    python -m repro.launch.serve --arch gemma3-1b --reduced --devices 8 \
        --batch 4 --prompt-len 16 --gen 8 [--sample --temperature 0.8 \
        --top-k 40 --top-p 0.95] [--eos-id 1] \
        [--speculate-k 4 --draft-layers 2 | --speculate-k 4 \
         --draft-config gemma3-1b]

``--continuous`` switches to the paged continuous-batching engine
(``repro.serve.ContinuousEngine``, DESIGN.md Sec. 14): requests stream
in on a seeded Poisson arrival trace and are admitted into decode slots
as they free up.

    python -m repro.launch.serve --arch gemma3-1b --reduced --continuous \
        --requests 32 --arrival-rate 0.5 --trace-seed 0 --slots 4 \
        --page-size 8 --prompt-len 48 --gen 8 \
        [--speculate-k 4 --draft-layers 2] [--prefill-batch 2]

EVERY shape that becomes a compile key — prompt padding, engine bucket
list, trace prompt-length range — is derived through
:func:`plan_shapes` from ``repro.serve.prompt_buckets`` / ``bucket_for``
(the engine uses the same helpers), so the CLI and the engine cannot
disagree on compile keys.  Timing is reported honestly: the first
engine call includes XLA compilation and is reported as such; a warm-up
precedes the timed region, whose steady-state tokens/s is what the
engine actually serves at.
"""
import argparse

from repro.launch.env import enable_compile_cache, set_host_device_count


def plan_shapes(prompt_len: int, page_size: int = 8):
    """Single source for the shape decisions that become compile keys:
    the bucket list covering prompts up to ``prompt_len`` and the
    (bucketed) padded length of a ``prompt_len`` prompt.  Both the CLI
    and the engines route through these helpers — nothing else in the
    launcher may invent a shape."""
    from repro.serve import bucket_for, prompt_buckets
    buckets = prompt_buckets(max(prompt_len, page_size),
                             min_bucket=page_size)
    return buckets, bucket_for(prompt_len, buckets)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--devices", type=int, default=0)
    ap.add_argument("--mesh-model", type=int, default=1)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=16,
                    help="max prompt length; rounded up to the bucketed "
                         "compile length from plan_shapes")
    ap.add_argument("--gen", type=int, default=8)
    ap.add_argument("--sample", action="store_true",
                    help="sample instead of greedy argmax")
    ap.add_argument("--temperature", type=float, default=1.0)
    ap.add_argument("--top-k", type=int, default=0,
                    help="truncate sampling to the k most likely tokens "
                         "(0 = full vocab)")
    ap.add_argument("--top-p", type=float, default=0.0,
                    help="nucleus sampling mass (0 or 1 = disabled; "
                         "composes with --top-k)")
    ap.add_argument("--eos-id", type=int, default=-1,
                    help="stop token id (>= 0 enables the done-mask "
                         "early exit)")
    ap.add_argument("--seed", type=int, default=0)
    # speculative decoding (DESIGN.md Sec. 15)
    ap.add_argument("--speculate-k", type=int, default=0,
                    help="draft k tokens per round and verify them in one "
                         "ragged pass (0 = plain decoding)")
    ap.add_argument("--draft-layers", type=int, default=0,
                    help="[speculative] early-exit depth of the "
                         "self-speculative draft (0 = num_blocks // 2)")
    ap.add_argument("--draft-config", default="",
                    help="[speculative, fixed-batch] arch name of a "
                         "separate draft model (mutually exclusive with "
                         "--draft-layers)")
    ap.add_argument("--prefill-batch", type=int, default=1,
                    help="[continuous] admit up to this many same-bucket "
                         "requests per prefill dispatch")
    # continuous-batching frontend
    ap.add_argument("--continuous", action="store_true",
                    help="continuous-batching paged engine instead of the "
                         "fixed-batch engine")
    ap.add_argument("--requests", type=int, default=32,
                    help="[continuous] number of requests in the trace")
    ap.add_argument("--arrival-rate", type=float, default=0.5,
                    help="[continuous] Poisson arrivals per decode step")
    ap.add_argument("--trace-seed", type=int, default=0,
                    help="[continuous] seed of the arrival/prompt trace")
    ap.add_argument("--slots", type=int, default=4,
                    help="[continuous] lockstep decode slots")
    ap.add_argument("--page-size", type=int, default=8,
                    help="[continuous] KV positions per cache page")
    args = ap.parse_args()

    if args.devices:
        set_host_device_count(args.devices, strict=True)
    enable_compile_cache()

    import time

    import jax
    import jax.numpy as jnp

    from repro.configs import get_config
    from repro.models import model as M
    from repro.serve import SamplingParams

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    dtype = jnp.float32 if args.reduced else jnp.bfloat16

    # Independent streams for init / prompts / frontend stubs / sampling —
    # reusing one key would correlate the prompt tokens with the weight
    # init (and the sampled continuations with both).
    k_init, k_prompt, k_front, k_sample = jax.random.split(
        jax.random.PRNGKey(args.seed), 4)
    params = M.init(cfg, k_init, dtype)
    sampling = SamplingParams(
        mode="sample" if args.sample else "greedy",
        temperature=args.temperature,
        top_k=args.top_k if args.top_k > 0 else None,
        top_p=args.top_p if 0.0 < args.top_p < 1.0 else None)
    eos_id = args.eos_id if args.eos_id >= 0 else None

    if args.continuous:
        _run_continuous(args, cfg, params, sampling, eos_id, dtype, k_sample)
        return

    from repro.launch.mesh import make_mesh
    from repro.models.frontends import (stub_audio_frontend,
                                        stub_vision_frontend)
    from repro.serve import make_engine

    nd = len(jax.devices())
    mesh = make_mesh((nd // args.mesh_model, args.mesh_model),
                     ("data", "model"))
    _, padded_len = plan_shapes(args.prompt_len)
    if padded_len != args.prompt_len:
        print(f"prompt-len {args.prompt_len} -> bucket {padded_len} "
              f"(compile keys come from plan_shapes)")
    B = args.batch
    npfx = 0
    batch = {"tokens": jax.random.randint(k_prompt, (B, padded_len), 0,
                                          cfg.vocab_size)}
    if cfg.frontend == "audio":
        batch["frames"] = stub_audio_frontend(k_front, B, cfg.d_model, dtype,
                                              frames=16)
    elif cfg.frontend == "vision":
        batch["prefix_embeds"] = stub_vision_frontend(k_front, B, cfg.d_model,
                                                      dtype, patches=16)
        npfx = 16

    draft_cfg = draft_params = None
    if args.draft_config:
        draft_cfg = get_config(args.draft_config)
        if args.reduced:
            draft_cfg = draft_cfg.reduced()
        draft_params = M.init(draft_cfg, jax.random.fold_in(k_init, 1),
                              dtype)
    engine = make_engine(
        cfg, mesh, batch=B, prompt_len=padded_len, max_new=args.gen,
        sampling=sampling, eos_id=eos_id, prefix_len=npfx,
        param_dtype=dtype, cache_dtype=dtype,
        speculate_k=args.speculate_k,
        draft_layers=args.draft_layers or None, draft_cfg=draft_cfg)

    # Warm-up call: compiles prefill + the whole generation scan.  The
    # historical launcher timed ms/token INCLUDING this first-call
    # compile, which made the steady-state number meaningless.
    t0 = time.time()
    res = engine.generate_with_state(params, batch, key=k_sample,
                                     draft_params=draft_params)
    jax.block_until_ready(res.tokens)
    t_compile = time.time() - t0

    t0 = time.time()
    res = engine.generate_with_state(params, batch, key=k_sample,
                                     draft_params=draft_params)
    jax.block_until_ready(res.tokens)
    dt = time.time() - t0

    print("generated token ids:")
    for row in res.tokens:
        print("  ", list(map(int, row)))
    n_tok = int(res.lengths.sum())
    print(f"first call (incl. compile): {t_compile:.2f}s")
    print(f"steady state: {dt:.3f}s for {n_tok} tokens "
          f"({n_tok / dt:.1f} tok/s, {dt / args.gen * 1e3:.1f} ms/step, "
          f"batch {B}, 1 executable call for the decode phase)")
    if eos_id is not None:
        print(f"done mask: {list(map(bool, res.done))}  "
              f"lengths: {list(map(int, res.lengths))}")
    if res.spec is not None:
        import numpy as np
        rounds = int(np.asarray(res.spec.rounds).sum())
        drafted = int(np.asarray(res.spec.drafted).sum())
        accepted = int(np.asarray(res.spec.accepted).sum())
        print(f"speculative: k={args.speculate_k}, {rounds} rounds, "
              f"acceptance {accepted}/{drafted} "
              f"({accepted / max(drafted, 1):.2f}); "
              f"{n_tok / max(rounds, 1):.2f} tokens per sequential pass")


def _run_continuous(args, cfg, params, sampling, eos_id, dtype,
                    k_sample) -> None:
    import jax

    from repro.models.model import PagedCacheLayout
    from repro.serve import ContinuousEngine, poisson_trace

    if args.draft_config:
        raise SystemExit("--draft-config is fixed-batch only; the "
                         "continuous engine speculates self-speculatively "
                         "(--draft-layers)")
    buckets, max_bucket = plan_shapes(args.prompt_len, args.page_size)
    # verify-window headroom: a speculative round writes up to
    # speculate_k rows past the last committed position
    max_pages = -(-(max_bucket + args.gen + args.speculate_k)
                  // args.page_size)
    layout = PagedCacheLayout(
        page_size=args.page_size,
        num_pages=args.slots * max_pages + 1,   # +1: reserved scratch page
        max_pages_per_slot=max_pages)
    trace = poisson_trace(args.requests, rate=args.arrival_rate,
                          seed=args.trace_seed, min_prompt=4,
                          max_prompt=args.prompt_len,
                          vocab_size=cfg.vocab_size)
    engine = ContinuousEngine(
        cfg, slots=args.slots, layout=layout, max_new=args.gen,
        buckets=buckets, sampling=sampling, eos_id=eos_id,
        param_dtype=dtype, cache_dtype=dtype,
        speculate_k=args.speculate_k,
        draft_layers=args.draft_layers or None
        if args.speculate_k else None,
        prefill_batch=args.prefill_batch)

    out = engine.run(params, trace, base_key=k_sample)
    s = out["stats"]
    print(f"continuous trace: {s['requests']} requests, "
          f"{s['generated_tokens']} tokens in {s['steps']} decode steps")
    print(f"  executables: {s['executables']} "
          f"(buckets used {s['buckets_used']} + 1 decode; "
          f"bound = {len(buckets)} buckets x {args.prefill_batch} "
          f"group sizes + 1 = {len(buckets) * args.prefill_batch + 1})")
    print(f"  slot utilization: {s['slot_utilization']:.2f}  "
          f"queue wait p50/p99: {s['wait_p50_steps']:.1f}/"
          f"{s['wait_p99_steps']:.1f} steps")
    print(f"  queue wait p50/p99: {s['wait_p50_ms']:.1f}/"
          f"{s['wait_p99_ms']:.1f} ms  time to first token p50/p99: "
          f"{s['ttft_p50_ms']:.1f}/{s['ttft_p99_ms']:.1f} ms  gap between "
          f"tokens p50/p99: {s['itl_p50_ms']:.1f}/{s['itl_p99_ms']:.1f} ms")
    for kind in ("prefill", "decode"):
        calls = [c for c in engine.record if c.kind == kind]
        if calls:
            mean = sum(c.t_ready - c.t_dispatch for c in calls) / len(calls)
            group = sum(c.group for c in calls) / len(calls)
            print(f"  {kind}: {len(calls)} calls, mean {mean * 1e3:.1f} ms "
                  f"dispatch to output on the host, {group:.1f} "
                  f"{'requests' if kind == 'prefill' else 'slots'} a call")
    if "speculative" in s:
        sp = s["speculative"]
        print(f"  speculative: k={args.speculate_k}, {sp['rounds']} rounds, "
              f"acceptance {sp['acceptance_rate']:.2f}, "
              f"{sp['tokens_per_round']:.2f} tokens/round")
    for rid in sorted(out["results"])[:4]:
        r = out["results"][rid]
        print(f"  req {rid}: {list(map(int, r.tokens))}")


if __name__ == "__main__":
    main()
