"""Driver of a continuous-serving cell: ``repro.serve.ContinuousEngine``,
greedy, with the weights and the page pools in the served dtype.

Set-up compiles the engine's own prefill (one per bucket of the mix) and
decode ahead of time and installs thin wrappers around them in the
engine.  A wrapper calls the compiled program, stamps the host time at
which its output is ready (the engine reads that output next, so this
adds no wait), and keeps its output and a host copy of its small inputs
(prompt, block table, positions: a few KB), read only after the window.  Set-up also runs each compiled program once on the
scratch page.  The window opens when the engine has made the mix's
``warm_steps`` decode steps, by when every slot is busy and the first
requests have retired, and closes at the first call that ends
``seconds`` later.  A traced run then records the mix's
``trace_seconds`` more under the profiler, so that the host-clock
numbers of both kinds of run come from an untraced window of the same
length.  At the close the wrapper ends ``run`` without draining the
queue.
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from . import common, reference, traffic
from . import weights as W
from .program import dtype_of, program_config


class WindowClosed(Exception):
    pass


def _host(*arrays):
    """Host copies of a call's inputs, taken once its output is ready and
    before the engine reuses its host arrays for the next call (on the
    CPU an input may share the engine's array)."""
    return tuple(np.array(a) for a in arrays)


class Recorder:
    """The wrappers' shared log and the window's clock: the window runs
    from ``t_open`` to ``t_mid``, and the traced part, where there is
    one, from ``t_mid`` to ``t_close``."""

    def __init__(self, warm_steps: int, seconds: float, on_open,
                 trace_seconds: float = 0.0, on_trace=None):
        self.warm_steps, self.seconds, self.on_open = warm_steps, seconds, on_open
        self.trace_seconds, self.on_trace = trace_seconds, on_trace
        self.decodes = 0
        self.calls = []           # (kind, t_start, t_ready, inputs, outputs)
        self.t_open = self.t_mid = self.t_close = None

    def _after(self, t_ready):
        if self.t_open is None:
            if self.decodes >= self.warm_steps:
                self.t_open = t_ready
                self.on_open()
        elif self.t_mid is None:
            if t_ready - self.t_open >= self.seconds:
                self.t_mid = t_ready
                if not self.trace_seconds:
                    self.t_close = t_ready
                    raise WindowClosed
                self.on_trace()
        elif t_ready - self.t_mid >= self.trace_seconds:
            self.t_close = t_ready
            raise WindowClosed

    def prefill(self, compiled):
        def call(params, pools, tokens, plen, pidx, keys):
            t0 = time.perf_counter()
            with common.span("bench.prefill"):
                tok, pools = compiled(params, pools, tokens, plen, pidx, keys)
                tok.block_until_ready()
            t1 = time.perf_counter()
            self.calls.append(("prefill", t0, t1, _host(tokens, plen, pidx),
                               tok))
            self._after(t1)
            return tok, pools
        return call

    def decode(self, compiled):
        def call(params, pools, table, tok, pos, keys):
            t0 = time.perf_counter()
            with common.span("bench.decode"):
                nxt, pools = compiled(params, pools, table, tok, pos, keys)
                nxt.block_until_ready()
            t1 = time.perf_counter()
            self.calls.append(("decode", t0, t1, _host(table, pos), nxt))
            self.decodes += 1
            self._after(t1)
            return nxt, pools
        return call

    def replay(self, prompts: dict):
        """Per request: its served tokens with their ready times, and the
        per-call records the per-layer metrics read."""
        owner, served, calls = {}, {}, []
        for kind, t0, t1, inputs, out in self.calls:
            out = np.asarray(out)
            if kind == "prefill":
                tokens, plen, pidx = inputs
                n = int(plen[0])
                rid = prompts[tuple(int(x) for x in tokens[0, :n])]
                owner[int(pidx[0, 0])] = rid
                served[rid] = [(int(out[0]), t1)]
                calls.append(("prefill", t0, t1, n))
            else:
                table, pos = inputs
                active = []
                for i, page in enumerate(table[:, 0]):
                    if page:
                        served[owner[int(page)]].append((int(out[i]), t1))
                        active.append(int(pos[i]) + 1)
                calls.append(("decode", t0, t1, active,
                              [int(p) + 1 for p in pos]))
        return served, calls


class ServeCell:
    def __init__(self, cell, devices):
        from repro.models import model as M
        from repro.models.model import PagedCacheLayout
        from repro.serve import ContinuousEngine

        model, mix = self.model, self.mix = cell.model, cell.mix
        self.cfg = program_config(model, cell.arch)
        self.devices = [devices[0]]
        dt = dtype_of(model)
        ps = mix["page_size"]
        layout = PagedCacheLayout(page_size=ps, num_pages=mix["num_pages"],
                                  max_pages_per_slot=mix["pages_per_slot"])
        with jax.default_device(devices[0]):
            self.engine = ContinuousEngine(
                self.cfg, slots=mix["slots"], layout=layout,
                max_new=mix["max_new"], buckets=tuple(mix["buckets"]),
                param_dtype=dt, cache_dtype=dt)
        single = M.param_specs(self.cfg, dt)
        std = mix["weights"]

        def init_params(key_data):
            return W.make_tree(key_data, single, std)

        self.init_params = jax.jit(init_params).lower(
            jax.ShapeDtypeStruct((2,), jnp.uint32)).compile()
        sds = lambda t: jax.tree.map(  # noqa: E731
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), t)
        p_sds, pool_sds = sds(single), sds(self.engine.pools)
        i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32)  # noqa: E731
        u32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.uint32)  # noqa: E731
        self.prefills = {
            bl: self.engine._get_prefill(bl, 1).lower(
                p_sds, pool_sds, i32(1, bl), i32(1), i32(1, bl // ps),
                u32(1, 2)).compile()
            for bl in mix["buckets"]}
        b, maxp = mix["slots"], mix["pages_per_slot"]
        self.decode = self.engine._get_decode().lower(
            p_sds, pool_sds, i32(b, maxp), i32(b), i32(b), u32(b, 2)).compile()

    def warm(self, params):
        """Run every compiled program once, writing only the scratch page,
        so that no program's first run falls in the window."""
        eng, ps = self.engine, self.mix["page_size"]
        # hold one pool at a time, as the engine does: a pool is 4 GB
        pools, eng.pools = eng.pools, None
        for bl, compiled in self.prefills.items():
            z = np.zeros((1, bl), np.int32)
            tok, pools = compiled(params, pools, z, np.array([bl], np.int32),
                                  np.zeros((1, bl // ps), np.int32),
                                  np.zeros((1, 2), np.uint32))
        b, maxp = self.mix["slots"], self.mix["pages_per_slot"]
        z = np.zeros((b,), np.int32)
        nxt, pools = self.decode(params, pools, np.zeros((b, maxp), np.int32),
                                 z, z, np.zeros((b, 2), np.uint32))
        jax.block_until_ready((tok, nxt))
        eng.pools = pools

    def install(self, rec: Recorder):
        for bl, compiled in self.prefills.items():
            self.engine._prefill_fns[(bl, 1)] = rec.prefill(compiled)
        self.engine._decode_fn = rec.decode(self.decode)


def compare(ref, mix, seed, prompts_by_rid, served, *, control=None):
    """Widest gap by which a served token's logit lies below the best of
    the reference ``ref`` (a ``reference.Reference``), over a seeded
    sample of finished requests that holds the one with the longest
    prompt.  ``control``, the reference in fp8, adds the same gap for the
    tokens that it puts first."""
    max_new = mix["max_new"]
    done = sorted(r for r, toks in served.items() if len(toks) == max_new)
    longest = max(done, key=lambda r: (len(prompts_by_rid[r]), -r))
    rest = [r for r in done if r != longest]
    rng = np.random.default_rng(seed)
    pick = [longest] + [int(r) for r in rng.choice(
        rest, size=min(mix["check_requests"] - 1, len(rest)), replace=False)]
    # one width for every sample, so that the reference compiles once
    width = -(-(mix["prompt"]["max"] + max_new) // 128) * 128
    seqs = np.zeros((len(pick), width), np.int32)
    where = np.zeros((len(pick), max_new), np.int32)
    toks = np.zeros((len(pick), max_new), np.int32)
    for j, r in enumerate(pick):
        prompt = prompts_by_rid[r]
        out = [t for t, _ in served[r]]
        seq = list(prompt) + out[:-1]
        seqs[j, :len(seq)] = seq
        where[j] = len(prompt) - 1 + np.arange(max_new)
        toks[j] = out
    sets = [toks]
    if control is not None:
        _, top = control.serve_gaps(seed, seqs, where, [])
        sets.append(top)
    gaps, _ = ref.serve_gaps(seed, seqs, where, sets)
    nums = {"served_logit_gap": float(gaps[0].max())}
    if control is not None:
        nums["control_logit_gap"] = float(gaps[1].max())
    return nums, {"requests": len(pick), "tokens": int(toks.size)}


def serve_once(sc: ServeCell, seed: int, seconds: float, on_open=None,
               trace_seconds: float = 0.0, on_trace=None):
    """Serve the mix from the seed until the window (and its traced part,
    where ``trace_seconds``) closes; returns the recorder, each request's
    served tokens with their times, the calls' records, and each
    request's prompt by id."""
    from repro.serve import Request
    from repro.serve.paged import PagePool

    mix, model = sc.mix, sc.model
    reqs = traffic.serve_requests(mix, seed, model["vocab_size"])
    prompts = {toks: rid for rid, toks, _ in reqs}
    if len(prompts) != len(reqs):
        raise ValueError("two requests share a prompt; the replay could not "
                         "tell them apart")
    rec = Recorder(mix["warm_steps"], seconds, on_open or (lambda: None),
                   trace_seconds, on_trace)
    sc.install(rec)
    sc.engine.page_pool = PagePool(mix["num_pages"])
    params = sc.init_params(W.seed_key_data(seed))
    sc.warm(params)
    try:
        sc.engine.run(params, [Request(rid=rid, tokens=toks, arrival=arr)
                               for rid, toks, arr in reqs])
    except WindowClosed:
        pass
    else:
        raise RuntimeError("the trace drained before the window closed; the "
                           "mix needs more requests")
    finally:
        del params
    served, calls = rec.replay(prompts)
    return rec, served, calls, {rid: toks for rid, toks, _ in reqs}


def run(cell, args, devices, peak, on_setup_done):
    mix, model = cell.mix, cell.model
    sc = ServeCell(cell, devices)
    profile = common.Profile(bool(args.trace))
    counter = common.CompileCounter()
    watch = counter.watching()
    state = {}

    def on_open():
        state["setup_s"] = on_setup_done()
        watch.__enter__()

    try:
        rec, served, calls, prompts_by_rid = serve_once(
            sc, args.seed, args.seconds, on_open,
            mix["trace_seconds"] if args.trace else 0.0, profile.start)
    finally:
        profile.stop()
        if "setup_s" in state:
            watch.__exit__(None, None, None)
    mem = common.peak_bytes(sc.devices)
    del sc
    t_open, t_mid, t_close = rec.t_open, rec.t_mid, rec.t_close
    window_s = t_mid - t_open
    emitted, gaps = 0, []
    for toks in served.values():
        times = [t for _, t in toks]
        emitted += sum(t_open < t <= t_mid for t in times)
        gaps += [b - a for a, b in zip(times, times[1:])
                 if a >= t_open and b <= t_mid]
    in_window = [c for c in calls if t_open < c[2] <= t_mid]
    ready = [t_open] + [c[2] for c in in_window]
    longest = max(range(1, len(ready)), key=lambda i: ready[i] - ready[i - 1])
    out = {
        "e2e": {"serve_tokens_per_s": emitted / window_s,
                "serve_itl_p99_ms": float(np.percentile(gaps, 99)) * 1e3,
                "setup_s": state["setup_s"]},
        "ctx": {"kind": "serve", "window_s": window_s, "calls": in_window,
                "traced_calls": [c for c in calls if t_mid < c[2] <= t_close],
                "peak": peak, "model": model, "arch": cell.arch, "mix": mix,
                "chips": 1,
                "trace_path": profile.path, "devices": [devices[0].id],
                "emitted": emitted, "gaps": len(gaps)},
        "notes": {"longest_call_gap_ms": 1e3 * (ready[longest]
                                                 - ready[longest - 1]),
                  "longest_at_s": ready[longest] - t_open,
                  "prefills": sum(1 for c in in_window if c[0] == "prefill"),
                  "gaps": len(gaps)},
        "profile": profile, "memory_peak_bytes": mem,
        "window_compiles": counter.count,
        "attempted": sum(1 for c in calls if c[0] == "prefill"),
        "failed": 0,
    }
    out["numbers"], out["checked"] = compare(
        reference.Reference(cell.arch, model, mix), mix, args.seed,
        prompts_by_rid, served)
    return out
