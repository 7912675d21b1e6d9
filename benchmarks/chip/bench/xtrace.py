"""Reduce a profiler trace (``.xplane.pb``) to the numbers the per-layer
metrics read.

The TPU trace has one plane per chip (``/device:TPU:<id>``).  Its
``XLA Ops`` line holds one event per HLO operation run, named by the
operation's HLO text (``%fused_dsgd_pallas.11 = (bf16[...]) custom-call(
...``); a ``while`` loop's event encloses the events of its body, so
only events that enclose no other count as operations.  A Pallas kernel
is a ``custom-call`` named after the jitted function that called
``pallas_call`` (``flash_attention_pallas``, ``fused_dsgd_pallas``...).
The host plane's ``python3`` line holds the harness's own spans
(``bench.*``), which bound the window, and every Python call under them.

``reduce`` returns, averaged over the chips where so stated:

* ``window_s``: from the first ``bench.*`` span to the last one's end;
* ``busy_s``: the union of operation intervals in the window (average);
* ``kernels``: per kernel name, events and seconds summed over chips;
* ``collective_s`` / ``collective_exposed_s``: per chip, the time of
  collective operations (the ops themselves and their asynchronous
  spans on the ``Async XLA Ops`` line), and the part of it with no
  other operation running on that chip;
* ``modules``: executions of each compiled program (first chip);
* ``breakdown``: the ten operations that took most time (seconds per
  chip), and the ten longest idle gaps named by the innermost host span
  that was open at the middle of each and by when, from the window's
  start, that middle fell.
"""
from __future__ import annotations

import collections
import re

_OP = re.compile(r"%?([A-Za-z_][\w\-]*?)(?:\.\d+)?\s*=")
_COLLECTIVE = ("collective-permute", "all-reduce", "all-gather",
               "reduce-scatter", "all-to-all", "send", "recv")


def op_base(name: str) -> str:
    """``%fused_dsgd_pallas.11 = (...)`` -> ``fused_dsgd_pallas``."""
    m = _OP.match(name)
    return m.group(1) if m else name.split(" ", 1)[0].lstrip("%")


def op_label(name: str) -> str:
    """Short stable label of an operation: its name and first result."""
    head = name.lstrip("%")
    op, _, rest = head.partition(" = ")
    return f"{op} {rest.split(' ', 1)[0].split('{', 1)[0]}".strip()


def is_collective(base: str) -> bool:
    return any(base.startswith(c) for c in _COLLECTIVE)


def union(intervals):
    """Merge (start, end) intervals; returns the sorted disjoint list."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


def total(merged) -> float:
    return sum(e - s for s, e in merged)


def subtract(a, b):
    """Length of the merged intervals ``a`` not covered by merged ``b``."""
    left, j = 0.0, 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                left += b[k][0] - cur
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            left += e - cur
    return left


def leaves(events):
    """Events that enclose no other event (events sorted by start)."""
    out = []
    for i, (name, s, e) in enumerate(events):
        nxt = events[i + 1][1] if i + 1 < len(events) else None
        if nxt is not None and nxt < e:
            continue
        out.append((name, s, e))
    return out


def _events(line, lo=None, hi=None):
    evs = []
    for ev in line.events:
        s = ev.start_ns
        e = s + ev.duration_ns
        if lo is not None and (e <= lo or s >= hi):
            continue
        if lo is not None:
            s, e = max(s, lo), min(e, hi)
        evs.append((ev.name, s, e))
    evs.sort(key=lambda x: (x[1], -x[2]))
    return evs


def _line(plane, name):
    for line in plane.lines:
        if line.name == name:
            return line
    return None


def host_spans(space):
    """Events of the host thread that holds the harness's spans (the
    Python main thread, ``python3``), and those spans."""
    plane = space.find_plane_with_name("/host:CPU")
    for line in plane.lines if plane is not None else []:
        spans = _events(line)
        bench = [x for x in spans if x[0].startswith("bench.")]
        if bench:
            return spans, bench
    return [], []


def name_gap(spans, t) -> str:
    best = None
    for name, s, e in spans:
        if s <= t < e and (best is None or e - s < best[1] - best[0]):
            best = (s, e, name)
    return best[2] if best else "no host span"


def reduce(path: str, device_ids) -> dict:
    from jax.profiler import ProfileData
    space = ProfileData.from_file(path)
    spans, bench = host_spans(space)
    if not bench:
        raise ValueError("the trace holds no bench.* host span")
    lo, hi = min(s for _, s, _ in bench), max(e for _, _, e in bench)
    window = (hi - lo) * 1e-9
    busy, coll, exposed, gaps = [], [], [], []
    kernels = collections.defaultdict(lambda: {"count": 0, "seconds": 0.0})
    ops = collections.Counter()
    modules = collections.Counter()
    chips = 0
    for dev in device_ids:
        plane = space.find_plane_with_name(f"/device:TPU:{dev}")
        line = _line(plane, "XLA Ops") if plane is not None else None
        if line is None:
            raise ValueError(f"the trace holds no XLA Ops of TPU {dev}")
        chips += 1
        evs = _events(line, lo, hi)
        leaf = leaves(evs)
        merged = union([(s, e) for _, s, e in evs])
        busy.append(total(merged) * 1e-9)
        c_iv, o_iv = [], []
        for name, s, e in leaf:
            base = op_base(name)
            (c_iv if is_collective(base) else o_iv).append((s, e))
            ops[op_label(name)] += (e - s) * 1e-9
            if "custom-call(" in name and "pallas" in base:
                kernels[base]["count"] += 1
                kernels[base]["seconds"] += (e - s) * 1e-9
        async_line = _line(plane, "Async XLA Ops")
        for name, s, e in (_events(async_line, lo, hi) if async_line else []):
            if is_collective(op_base(name)):
                c_iv.append((s, e))
        c_m = union(c_iv)
        coll.append(total(c_m) * 1e-9)
        exposed.append(subtract(c_m, union(o_iv)) * 1e-9)
        prev = lo
        for s, e in merged + [[hi, hi]]:
            if s > prev:
                gaps.append((s - prev, (s + prev) / 2))
            prev = max(prev, e)
        if chips == 1:
            mods = _line(plane, "XLA Modules")
            for name, _, _ in (_events(mods, lo, hi) if mods else []):
                modules[name.split("(", 1)[0]] += 1
    gaps.sort(reverse=True)
    return {
        "window_s": window,
        "busy_s": sum(busy) / chips,
        "chips": chips,
        "kernels": dict(kernels),
        "collective_s": coll,
        "collective_exposed_s": exposed,
        "modules": dict(modules),
        "breakdown": {
            "device_ops": [[n, s / chips] for n, s in ops.most_common(10)],
            "idle_gaps": [[f"{name_gap(spans, mid)} at +"
                           f"{(mid - lo) * 1e-9:.3f}s", g * 1e-9]
                          for g, mid in gaps[:10]]},
    }
