"""Continuous-batching serve engine over a paged KV cache.

The fixed-batch engine (``serve.engine``) compiles one executable per
``(batch, prompt_len, max_new)`` and retires the WHOLE batch when its
last request finishes — the wrong shape for ragged production traffic.
This engine keeps a fixed set of ``slots`` decoding in lockstep while
requests stream through them:

* **Paged KV cache** — every layer's cache is a page pool
  ``(num_pages, page_size, KV, hd)`` shared by all slots; a slot owns
  pages only through its row of the int32 block table.  Retiring a
  request returns its pages to the :class:`~repro.serve.paged.PagePool`
  free list; admission takes them back.  Physical page 0 is the
  reserved scratch page idle slots write into (their lockstep decode
  output is discarded on the host).
* **Slot scheduler** — the per-step host loop admits queued requests
  into free slots (arrival time permitting, pages permitting), runs ONE
  batched paged decode step for all slots, then retires slots that hit
  eos or their token budget.  The historical in-graph done-mask becomes
  the host-side free-slot map.
* **Bucketed prefill** — prompts are right-padded to the power-of-two
  buckets from :func:`~repro.serve.paged.prompt_buckets` and prefilled
  straight into their slots' pages (the padded tail writes garbage K/V
  that decode overwrites position-by-position before ``k_valid_len``
  ever exposes it).  With ``prefill_batch > 1`` up to that many
  queue-head requests sharing a bucket are admitted in ONE dispatch (an
  in-graph scan of the per-request prefill body, so tokens stay
  bit-identical to one-at-a-time admission).  The lifetime executable
  count stays bounded by ``len(buckets) + 1`` per admission batch size
  actually seen (one prefill per (bucket, group size) + one decode),
  pinned by ``dispatch_counter``.
* **Speculative decoding** — with ``speculate_k > 0`` the lockstep
  decode step becomes a draft-``k``-verify-once round (DESIGN.md
  Sec. 15): ``k`` early-exit draft steps through the first
  ``draft_layers`` blocks, ONE ragged verify pass scoring all ``k+1``
  window rows, accept/reject and page-pool window rollback — all
  inside one executable.  The host advances each slot by its accepted
  count (1..k+1 tokens per step), so slot positions become ragged by
  construction; idle slots run the round against scratch page 0 and
  their output is discarded exactly as in the plain path.
* **Per-request PRNG** — streams are keyed by ``fold_in(base_key,
  request_id)`` at admission, NOT by slot index, and each sampled
  token folds in its absolute position; a refilled slot can never
  reuse a retired request's stream, and a request's tokens are
  bit-identical whether it runs alone or shares the batch
  (tests/test_serve_continuous.py pins both).

Single-host by design: admission decisions are inherently host-driven
(one small sync per step); the distributed fixed-batch engine stays the
multi-host path (DESIGN.md Sec. 10 vs Sec. 14).
"""
from __future__ import annotations

import time
from collections import deque
from dataclasses import dataclass, field
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import ops
from repro.models import model as M
from repro.models.model import PagedCacheLayout

from .paged import PagePool, Request, bucket_for, prompt_buckets
from .sampling import (DRAFT_STREAM, SamplingParams, fold_pos_keys,
                       sample_token, speculative_accept)


@dataclass
class _Slot:
    """Host-side lifecycle state of one decode slot (FREE when
    ``rid is None`` -> PREFILL/DECODE while owned -> retired back to
    FREE)."""
    rid: int | None = None
    pos: int = 0                 # next K/V write position (== length)
    generated: int = 0
    pages: list = field(default_factory=list)
    admitted_step: int = 0


def span(name: str):
    """A host span on the profiler's clock, beside the device's events in
    one trace; it costs about a microsecond when no profiler runs."""
    return jax.profiler.TraceAnnotation(name)


@dataclass
class RequestResult:
    rid: int
    tokens: list                 # generated ids (incl. terminating eos)
    arrival: float
    admitted_step: int
    finished_step: int
    # host times (time.perf_counter): the step counter first reached the
    # arrival, the prefill was dispatched, the first and the last token
    # were on the host
    t_eligible: float = 0.0
    t_admitted: float = 0.0
    t_first: float = 0.0
    t_done: float = 0.0

    @property
    def wait_steps(self) -> float:
        """Queueing delay in virtual decode-step units."""
        return self.admitted_step - self.arrival


@dataclass
class CallRecord:
    """One prefill or decode call of :meth:`ContinuousEngine.run`:
    ``t_dispatch`` just before the call, ``t_ready`` once its output is
    on the host.  A prefill names its bucket and the real prompt length
    of each request in its group; a decode names each active slot and
    the position it decodes at."""
    kind: str                    # "prefill" | "decode"
    bucket: int                  # prefill bucket length (0 for decode)
    t_dispatch: float
    t_ready: float
    prompt_lens: tuple = ()
    active: tuple = ()           # ((slot, position), ...)

    @property
    def group(self) -> int:
        """Requests prefilled, or slots decoded, by the call."""
        return len(self.prompt_lens) + len(self.active)


class ContinuousEngine:
    """See module docstring.  ``run`` consumes a list of
    :class:`~repro.serve.paged.Request` and returns per-request results
    plus deterministic scheduler statistics."""

    def __init__(self, cfg, *, slots: int, layout: PagedCacheLayout,
                 max_new: int, buckets=None, max_prompt: int = 48,
                 sampling: SamplingParams = SamplingParams(),
                 eos_id: int | None = None, param_dtype=jnp.float32,
                 cache_dtype=jnp.float32,
                 kernel_config: ops.KernelConfig | None = None,
                 speculate_k: int = 0, draft_layers: int | None = None,
                 prefill_batch: int = 1):
        if slots < 1:
            raise ValueError(f"need >= 1 slot, got {slots}")
        if speculate_k < 0:
            raise ValueError(f"speculate_k must be >= 0, got {speculate_k}")
        if prefill_batch < 1:
            raise ValueError(
                f"prefill_batch must be >= 1, got {prefill_batch}")
        self.speculate_k = speculate_k
        self.prefill_batch = prefill_batch
        if speculate_k:
            if draft_layers is None:
                draft_layers = max(1, cfg.num_blocks // 2)
            if not 0 <= draft_layers <= cfg.num_blocks:
                raise ValueError(
                    f"draft_layers {draft_layers} outside "
                    f"[0, {cfg.num_blocks}]")
        elif draft_layers is not None:
            raise ValueError("draft_layers requires speculate_k > 0")
        self.draft_layers = draft_layers
        self.cfg = cfg
        self.slots = slots
        self.layout = layout
        self.max_new = max_new
        self.buckets = tuple(buckets) if buckets is not None \
            else prompt_buckets(max_prompt)
        for b in self.buckets:
            if b % layout.page_size:
                raise ValueError(f"bucket {b} not a multiple of page_size "
                                 f"{layout.page_size}")
        if max(self.buckets) > layout.max_seq:
            raise ValueError(
                f"largest bucket {max(self.buckets)} exceeds per-slot "
                f"capacity {layout.max_seq}")
        self.sampling = sampling
        self.eos_id = eos_id
        self.cache_dtype = cache_dtype
        self.kcfg = ops.resolve_config(kernel_config)
        # eager init validates the arch (attn-family decoder-only) and
        # allocates the pools once — they live across requests
        self.pools = M.init_paged_cache(cfg, layout, cache_dtype)
        self.page_pool = PagePool(layout.num_pages)
        # lifetime executable registry: one prefill per (bucket,
        # admission-group size) actually seen + one decode.
        # dispatch_counter counts calls per executable; num_executables
        # is the gated compile-count model.
        self._prefill_fns: dict[tuple[int, int], Any] = {}
        self._decode_fn = None
        self.dispatch_counter: dict[str, int] = {}
        # the last run's calls, oldest first (cleared when a run starts;
        # kept on the engine so that a run cut short leaves it readable)
        self.record: list[CallRecord] = []

    # -- executables --------------------------------------------------

    @property
    def num_executables(self) -> int:
        return len(self._prefill_fns) + (self._decode_fn is not None)

    def _get_prefill(self, bl: int, nb: int = 1):
        """Jitted prefill-into-pages for bucket length ``bl`` and
        admission-group size ``nb``: ``(params, pools, tokens (nb, bl),
        prompt_len (nb,), page_idx (nb, npg), req_keys (nb, 2)) ->
        (first sampled tokens (nb,), pools)``.  ``prompt_len`` and
        ``page_idx`` are traced, so every prompt in the bucket reuses
        this executable.  The group is an in-graph ``lax.scan`` of the
        per-request body — ONE dispatch, but each request's numerics
        (and so its sampled tokens) are identical to admitting it
        alone."""
        fn = self._prefill_fns.get((bl, nb))
        if fn is not None:
            return fn
        cfg, kcfg, layout = self.cfg, self.kcfg, self.layout
        sampling, cache_dtype = self.sampling, self.cache_dtype
        ps = layout.page_size
        npg = bl // ps

        def prefill(params, pools, tokens, prompt_len, page_idx, req_keys):
            def one(pools, xs):
                toks1, plen1, pidx1, rkey1 = xs
                caches = M.init_cache(cfg, 1, bl, cache_dtype)
                h, caches, _ = M.backbone(cfg, params, toks1[None],
                                          caches=caches, cache_index=0,
                                          kernel_config=kcfg)
                # M.prefill's "last position" would be the padded row
                # bl-1; the prompt's real last row is prompt_len-1
                h_last = jax.lax.dynamic_index_in_dim(
                    h, plen1 - 1, axis=1, keepdims=False)       # (1, D)
                logits = h_last @ M._out_proj(cfg, params)
                if cfg.final_softcap is not None:
                    logits = cfg.final_softcap * jnp.tanh(
                        logits / cfg.final_softcap)
                keys = jax.random.fold_in(rkey1, plen1)[None] \
                    if sampling.needs_rng else None
                tok = sample_token(logits.astype(jnp.float32), sampling,
                                   keys)

                def pack(pool, dense):
                    if dense.ndim == 4:  # prologue leaf (1, bl, KV, hd)
                        v = dense[0].reshape((npg, ps) + dense.shape[2:])
                        return pool.at[pidx1].set(v.astype(pool.dtype))
                    # stacked blocks leaf (L, 1, bl, KV, hd)
                    nl = dense.shape[0]
                    v = dense[:, 0].reshape((nl, npg, ps)
                                            + dense.shape[3:])
                    return pool.at[:, pidx1].set(v.astype(pool.dtype))

                return jax.tree.map(pack, pools, caches), tok[0]

            pools, toks = jax.lax.scan(
                one, pools, (tokens, prompt_len, page_idx, req_keys))
            return toks, pools

        fn = jax.jit(prefill)
        self._prefill_fns[(bl, nb)] = fn
        name = f"prefill_{bl}" if nb == 1 else f"prefill_{bl}x{nb}"
        self.dispatch_counter.setdefault(name, 0)
        return fn

    def _get_decode(self):
        """Jitted lockstep decode over ALL slots: ``(params, pools,
        table (B, maxp), tok (B,), pos (B,), keys (B, 2)) ->
        (next token (B,), pools)``, or — with ``speculate_k > 0`` — one
        draft-k-verify-once round ``-> (emitted (B, k+1), counts (B,),
        pools)`` where each slot's first ``counts`` columns of
        ``emitted`` are its tokens this round (the host clips eos /
        budget; rejected window rows are already rolled back
        in-graph)."""
        if self._decode_fn is not None:
            return self._decode_fn
        cfg, kcfg, sampling = self.cfg, self.kcfg, self.sampling
        if self.speculate_k:
            k, dl = self.speculate_k, self.draft_layers
            ps = self.layout.page_size

            def spec_decode(params, pools, table, tok, pos, keys):
                win = pos[:, None] + jnp.arange(k + 1)       # (B, k+1)
                wpage = jnp.take_along_axis(table, win // ps, axis=1)
                wslot = win % ps

                def gather(pool):
                    if pool.ndim == 4:
                        return pool[wpage, wslot]
                    return pool[:, wpage, wslot]

                saved = jax.tree.map(gather, pools)

                def draft(carry, i):
                    pl, cur = carry
                    lg, pl = M.decode_step(cfg, params, pl, cur[:, None],
                                           pos + i, decode_mode="paged",
                                           block_table=table,
                                           draft_layers=dl,
                                           kernel_config=kcfg)
                    lg = lg[:, -1].astype(jnp.float32)
                    dk = fold_pos_keys(keys, pos + 1 + i, DRAFT_STREAM) \
                        if sampling.needs_rng else None
                    nxt = sample_token(lg, sampling, dk)
                    return (pl, nxt), (lg, nxt)

                (pools, _), (dlg, dtk) = jax.lax.scan(
                    draft, (pools, tok), jnp.arange(k))
                dlg = jnp.moveaxis(dlg, 0, 1)                # (B, k, V)
                dtk = jnp.moveaxis(dtk, 0, 1)                # (B, k)
                vt = jnp.concatenate([tok[:, None], dtk], axis=1)
                vlg, pools = M.decode_step(cfg, params, pools, vt, pos,
                                           decode_mode="paged",
                                           block_table=table,
                                           kernel_config=kcfg)
                acc, emit = speculative_accept(
                    vlg, dlg, dtk, sampling,
                    keys if sampling.needs_rng else None, pos + 1)
                m = acc + jnp.int32(1)
                keep = jnp.arange(k + 1)[None, :] < m[:, None]

                def restore(pool, s):
                    if pool.ndim == 4:
                        cur = pool[wpage, wslot]
                        mm = keep.reshape(
                            keep.shape + (1,) * (cur.ndim - 2))
                        return pool.at[wpage, wslot].set(
                            jnp.where(mm, cur, s))
                    cur = pool[:, wpage, wslot]
                    mm = keep.reshape(
                        (1,) + keep.shape + (1,) * (cur.ndim - 3))
                    return pool.at[:, wpage, wslot].set(
                        jnp.where(mm, cur, s))

                pools = jax.tree.map(restore, pools, saved)
                return emit, m, pools

            self._decode_fn = jax.jit(spec_decode)
            self.dispatch_counter.setdefault("decode", 0)
            return self._decode_fn

        def decode(params, pools, table, tok, pos, keys):
            logits, pools = M.decode_step(cfg, params, pools, tok[:, None],
                                          pos, decode_mode="paged",
                                          block_table=table,
                                          kernel_config=kcfg)
            skeys = jax.vmap(jax.random.fold_in)(keys, pos + 1) \
                if sampling.needs_rng else None
            nxt = sample_token(logits[:, -1].astype(jnp.float32), sampling,
                               skeys)
            return nxt, pools

        self._decode_fn = jax.jit(decode)
        self.dispatch_counter.setdefault("decode", 0)
        return self._decode_fn

    # -- scheduler ----------------------------------------------------

    def run(self, params, requests, *, base_key=None,
            max_steps: int = 100_000) -> dict:
        """Drive the trace to completion.  Returns ``{"results":
        {rid: RequestResult}, "stats": {...}}`` with deterministic
        scheduler statistics (virtual time = decode-step index) and the
        same delays in host milliseconds.  Each scheduler iteration runs
        under the host span ``serve.step`` and each fetch of a call's
        output under ``serve.sync``; ``self.record`` logs every call."""
        if base_key is None:
            base_key = jax.random.PRNGKey(0)
        layout = self.layout
        maxp = layout.max_pages_per_slot
        order = sorted(requests, key=lambda r: (r.arrival, r.rid))
        queue = deque(order)
        for r in queue:
            if r.prompt_len + self.max_new + self.speculate_k \
                    > layout.max_seq:
                raise ValueError(
                    f"request {r.rid}: prompt {r.prompt_len} + max_new "
                    f"{self.max_new} + speculate_k {self.speculate_k} "
                    f"exceeds slot capacity {layout.max_seq}")
        slots = [_Slot() for _ in range(self.slots)]
        table = np.zeros((self.slots, maxp), np.int32)   # row 0s = scratch
        last_tok = np.zeros((self.slots,), np.int32)
        keys = np.zeros((self.slots, 2), np.uint32)
        toks: dict[int, list] = {}
        results: dict[int, RequestResult] = {}
        step = 0
        busy_acc = 0
        spec_rounds = spec_accepted = 0
        self.record = record = []
        eligible = 0                     # order[:eligible] have arrived
        t_eligible: dict[int, float] = {}
        t_admitted: dict[int, float] = {}
        t_last: dict[int, float] = {}    # rid -> its last token's time
        t_first: dict[int, float] = {}
        gaps: list[float] = []           # between tokens of one request

        def retire(s: _Slot, fin_step: int):
            self.page_pool.free(s.pages)
            i = slots.index(s)
            table[i] = 0
            last_tok[i] = 0
            keys[i] = 0
            rid = s.rid
            results[rid] = RequestResult(
                rid=rid, tokens=toks.pop(rid), arrival=arrivals[rid],
                admitted_step=s.admitted_step, finished_step=fin_step,
                t_eligible=t_eligible.pop(rid),
                t_admitted=t_admitted.pop(rid), t_first=t_first.pop(rid),
                t_done=t_last.pop(rid))
            s.rid, s.pos, s.generated, s.pages = None, 0, 0, []

        def emitted(rid: int, n: int, t: float):
            # tokens of one call reach the host together: the first
            # closes a gap, the rest follow it at no distance
            gaps.append(t - t_last[rid])
            gaps.extend([0.0] * (n - 1))
            t_last[rid] = t

        arrivals = {r.rid: r.arrival for r in queue}

        while queue or any(s.rid is not None for s in slots):
            if step >= max_steps:
                raise RuntimeError(f"trace did not drain in {max_steps} "
                                   f"steps")
            with span("serve.step"):
                now = time.perf_counter()
                while eligible < len(order) \
                        and order[eligible].arrival <= step:
                    t_eligible[order[eligible].rid] = now
                    eligible += 1
                # -- admission: free slots pull arrived requests, grouped
                #    into one batched prefill dispatch per shared bucket
                free = [i for i, s in enumerate(slots) if s.rid is None]
                while free and queue and queue[0].arrival <= step \
                        and self.page_pool.available >= maxp:
                    group = []               # [(request, slot, pages)]
                    bl = None
                    while queue and queue[0].arrival <= step \
                            and len(group) < min(len(free),
                                                 self.prefill_batch) \
                            and self.page_pool.available >= maxp:
                        b = bucket_for(queue[0].prompt_len, self.buckets)
                        if bl is None:
                            bl = b
                        elif b != bl:        # next head needs another bucket
                            break
                        group.append((queue.popleft(), free.pop(0),
                                      self.page_pool.alloc(maxp)))
                    nb = len(group)
                    npg = bl // layout.page_size
                    padded = np.zeros((nb, bl), np.int32)
                    plen = np.zeros((nb,), np.int32)
                    pidx = np.zeros((nb, npg), np.int32)
                    rkeys = np.zeros((nb, 2), np.uint32)
                    for j, (r, i, pages) in enumerate(group):
                        padded[j, :r.prompt_len] = r.tokens
                        plen[j] = r.prompt_len
                        pidx[j] = pages[:npg]
                        table[i] = pages
                        rkeys[j] = np.asarray(
                            jax.random.fold_in(base_key, r.rid), np.uint32)
                        keys[i] = rkeys[j]
                    name = f"prefill_{bl}" if nb == 1 \
                        else f"prefill_{bl}x{nb}"
                    fn = self._get_prefill(bl, nb)
                    self.dispatch_counter[name] += 1
                    t_call = time.perf_counter()
                    tok, self.pools = fn(
                        params, self.pools, jnp.asarray(padded),
                        jnp.asarray(plen), jnp.asarray(pidx),
                        jnp.asarray(rkeys))
                    with span("serve.sync"):
                        tok = np.asarray(tok)
                    t_ready = time.perf_counter()
                    record.append(CallRecord(
                        "prefill", bl, t_call, t_ready,
                        prompt_lens=tuple(int(x) for x in plen)))
                    for j, (r, i, pages) in enumerate(group):
                        s = slots[i]
                        t0 = int(tok[j])
                        s.rid, s.pos, s.generated = r.rid, r.prompt_len, 1
                        s.pages, s.admitted_step = pages, step
                        toks[r.rid] = [t0]
                        t_admitted[r.rid] = t_call
                        t_first[r.rid] = t_last[r.rid] = t_ready
                        last_tok[i] = t0
                        if self.max_new == 1 or t0 == self.eos_id:
                            retire(s, step)
                # -- one lockstep decode step over all slots ----------
                active = [s.rid is not None for s in slots]
                if any(active):
                    busy_acc += sum(active)
                    fn = self._get_decode()
                    self.dispatch_counter["decode"] += 1
                    pos = np.array([s.pos for s in slots], np.int32)
                    live = tuple((i, s.pos) for i, s in enumerate(slots)
                                 if s.rid is not None)
                    t_call = time.perf_counter()
                    if self.speculate_k:
                        emit, cnt, self.pools = fn(
                            params, self.pools, jnp.asarray(table),
                            jnp.asarray(last_tok), jnp.asarray(pos),
                            jnp.asarray(keys))
                        with span("serve.sync"):
                            emit, cnt = np.asarray(emit), np.asarray(cnt)
                        t_ready = time.perf_counter()
                        for i, s in enumerate(slots):
                            if s.rid is None:
                                continue
                            m = int(cnt[i])
                            spec_rounds += 1
                            spec_accepted += m - 1
                            out = [int(t) for t in emit[i, :m]]
                            if self.eos_id is not None \
                                    and self.eos_id in out:
                                out = out[:out.index(self.eos_id) + 1]
                            out = out[:self.max_new - s.generated]
                            toks[s.rid].extend(out)
                            emitted(s.rid, len(out), t_ready)
                            s.pos += len(out)
                            s.generated += len(out)
                            last_tok[i] = out[-1]
                            if out[-1] == self.eos_id \
                                    or s.generated >= self.max_new:
                                retire(s, step)
                    else:
                        nxt, self.pools = fn(params, self.pools,
                                             jnp.asarray(table),
                                             jnp.asarray(last_tok),
                                             jnp.asarray(pos),
                                             jnp.asarray(keys))
                        with span("serve.sync"):
                            nxt = np.asarray(nxt)
                        t_ready = time.perf_counter()
                        for i, s in enumerate(slots):
                            if s.rid is None:
                                continue
                            t = int(nxt[i])
                            toks[s.rid].append(t)
                            emitted(s.rid, 1, t_ready)
                            s.pos += 1
                            s.generated += 1
                            last_tok[i] = t
                            if t == self.eos_id \
                                    or s.generated >= self.max_new:
                                retire(s, step)
                    record.append(CallRecord("decode", 0, t_call, t_ready,
                                             active=live))
            step += 1

        res = list(results.values())
        waits = np.array([r.wait_steps for r in res])
        lens = np.array([len(r.tokens) for r in res])

        def ms(q, xs):
            return float(np.percentile(xs, q)) * 1e3 if len(xs) else 0.0

        wait_s = [r.t_admitted - r.t_eligible for r in res]
        ttft_s = [r.t_first - r.t_eligible for r in res]
        stats = {
            "steps": step,
            "requests": len(results),
            "generated_tokens": int(lens.sum()),
            "slot_utilization": float(busy_acc / max(step * self.slots, 1)),
            "executables": self.num_executables,
            "buckets_used": sorted(
                {int(k.split("_")[1].split("x")[0])
                 for k in self.dispatch_counter
                 if k.startswith("prefill_")}),
            "wait_p50_steps": float(np.percentile(waits, 50)),
            "wait_p99_steps": float(np.percentile(waits, 99)),
            "wait_p50_ms": ms(50, wait_s),
            "wait_p99_ms": ms(99, wait_s),
            "ttft_p50_ms": ms(50, ttft_s),
            "ttft_p99_ms": ms(99, ttft_s),
            "itl_p50_ms": ms(50, gaps),
            "itl_p99_ms": ms(99, gaps),
            "dispatches": dict(self.dispatch_counter),
        }
        if self.speculate_k:
            stats["speculative"] = {
                "rounds": spec_rounds,
                "drafted": spec_rounds * self.speculate_k,
                "accepted": spec_accepted,
                "acceptance_rate": float(
                    spec_accepted / max(spec_rounds * self.speculate_k, 1)),
                "tokens_per_round": float(
                    (spec_rounds + spec_accepted) / max(spec_rounds, 1)),
            }
        return {"results": results, "stats": stats}
