"""Read the program's own names in a profiler trace: the train step's
named scopes on the device, and the serving engine's host spans.

Train.  ``make_train_step`` runs the forward under
``jax.named_scope("forward")``, the method's update under ``"update"``
and every gossip round inside it under ``"gossip"``.  XLA keeps these in
each operation's op name (``jit(_step)/shard_map/update/gossip/...``),
which the TPU trace gives as the ``tf_op`` stat of the operation's
``XLA Ops`` event.  JAX marks the backward itself: a transposed
operation's name holds ``transpose(jvp(forward))`` and the remat
recompute ``rematted_computation``.  ``classify`` checks, in order,
``gossip``, ``update``, those two markers (backward), ``forward``; the
rest is ``unscoped``.  Each operation counts its self time, the part of
its event that no event inside it covers (a ``while`` keeps only its
loop's own time), so that the classes add up to the device's busy time.

Serve.  ``ContinuousEngine.run`` opens ``serve.step`` around each
scheduler iteration and ``serve.sync`` around each fetch of a call's
output.

Both reductions keep to the window that the harness's ``bench.*`` spans
bound, and are cached per trace file, since several metrics read one.
"""
from __future__ import annotations

import collections
import re

from . import xtrace

OP_NAME_STAT = "tf_op"
CLASSES = ("forward", "backward", "update", "gossip", "unscoped")
BACKWARD_MARKERS = ("transpose(jvp(", "rematted_computation")

_DTYPE_BYTES = {"pred": 1, "s8": 1, "u8": 1, "f8e4m3fn": 1, "f8e5m2": 1,
                "bf16": 2, "f16": 2, "s16": 2, "u16": 2, "f32": 4, "s32": 4,
                "u32": 4, "f64": 8, "s64": 8, "u64": 8}
_ARRAY = re.compile(r"\b([a-z]+\d*[a-z\d]*)\[([\d,]*)\]")
_cache: dict = {}


def _scope(name: str, op_name: str) -> bool:
    return re.search(rf"(^|[/(;]){name}($|[/);:])", op_name) is not None


def classify(op_name: str) -> str:
    """The layer of an operation, from its op name."""
    if _scope("gossip", op_name):
        return "gossip"
    if _scope("update", op_name):
        return "update"
    if any(m in op_name for m in BACKWARD_MARKERS):
        return "backward"
    if _scope("forward", op_name):
        return "forward"
    return "unscoped"


def result_bytes(event_name: str) -> int:
    """Bytes of the arrays an operation's HLO text gives as its result
    (``%x = (f32[8,128]{1,0}, bf16[4]) op(...)``)."""
    head = event_name.partition(" = ")[2]
    depth, end = 0, len(head)
    for i, c in enumerate(head):           # the result type ends at the
        depth += c == "("                   # first space outside brackets
        depth -= c == ")"
        if c == " " and depth <= 0:
            end = i
            break
    total = 0
    for dtype, dims in _ARRAY.findall(head[:end]):
        n = 1
        for d in filter(None, dims.split(",")):
            n *= int(d)
        total += n * _DTYPE_BYTES.get(dtype, 0)
    return total


def self_times(events):
    """``events``: (name, start, end, op name) sorted by start, longest
    first on ties.  Returns (op name, name, self time) of each event:
    its length less what the events nested in it cover."""
    out, stack = [], []                 # stack: [index into out, end]
    for name, s, e, op in events:
        while stack and stack[-1][1] <= s:
            stack.pop()
        if stack:
            parent = stack[-1]
            out[parent[0]][2] -= min(e, parent[1]) - s
        out.append([op, name, e - s])
        stack.append([len(out) - 1, e])
    return out


def _varint(b, i):
    out = shift = 0
    while True:
        c = b[i]
        i += 1
        out |= (c & 0x7F) << shift
        shift += 7
        if c < 0x80:
            return out, i


def _fields(b):
    """(field number, value) of a serialized protobuf message; a
    length-delimited value is a memoryview of its bytes."""
    i, n = 0, len(b)
    while i < n:
        key, i = _varint(b, i)
        wire = key & 7
        if wire == 0:
            v, i = _varint(b, i)
        elif wire == 2:
            size, i = _varint(b, i)
            v, i = b[i:i + size], i + size
        elif wire in (1, 5):
            size = 8 if wire == 1 else 4
            v, i = b[i:i + size], i + size
        else:
            raise ValueError(f"protobuf wire type {wire}")
        yield key >> 3, v


def _entry(b):
    """The value of a protobuf map entry (field 2)."""
    return next(v for f, v in _fields(b) if f == 2)


def op_names(path: str, planes) -> dict:
    """{plane name: {event name: op name}}.  The TPU trace keeps an
    operation's ``tf_op`` stat on the event's metadata, which
    ``ProfileData`` does not expose, so this reads the planes' event
    metadata from the ``.xplane.pb`` itself (XSpace.planes = 1;
    XPlane.name = 2, event_metadata = 4, stat_metadata = 5;
    XEventMetadata.name = 2, stats = 5; XStat.metadata_id = 1,
    str_value = 5, ref_value = 7)."""
    with open(path, "rb") as f:
        data = memoryview(f.read())
    out = {}
    for field, plane in _fields(data):
        if field != 1:
            continue
        parts = list(_fields(plane))
        name = next((bytes(v).decode() for f, v in parts if f == 2), "")
        if name not in planes:
            continue
        stat_names = {}
        for f, v in parts:
            if f == 5:
                meta = dict(_fields(_entry(v)))
                stat_names[meta.get(1, 0)] = bytes(meta.get(2, b"")).decode()
        ops = out[name] = {}
        for f, v in parts:
            if f != 4:
                continue
            ev_name, op = "", ""
            for g, w in _fields(_entry(v)):
                if g == 2:
                    ev_name = bytes(w).decode(errors="replace")
                elif g == 5:
                    stat = dict(_fields(w))
                    if stat_names.get(stat.get(1)) != OP_NAME_STAT:
                        continue
                    op = bytes(stat[5]).decode(errors="replace") if 5 in stat \
                        else stat_names.get(stat.get(7), "")
            ops[ev_name] = op
    return out


def _op_events(line, lo, hi, ops):
    evs = []
    for ev in line.events:
        s = ev.start_ns
        e = s + ev.duration_ns
        if e <= lo or s >= hi:
            continue
        evs.append((ev.name, max(s, lo), min(e, hi), ops.get(ev.name, "")))
    evs.sort(key=lambda x: (x[1], -x[2]))
    return evs


def _window(space):
    spans, bench = xtrace.host_spans(space)
    if not bench:
        raise ValueError("the trace holds no bench.* host span")
    return spans, min(s for _, s, _ in bench), max(e for _, _, e in bench)


def _load(path):
    from jax.profiler import ProfileData
    return ProfileData.from_file(path)


def train(path: str, device_ids, steps: int) -> dict:
    """Per class, the device's self time a traced step in ms (mean over
    chips), with the ten largest operations of each class; and the
    gossip's collective-permutes: bytes a chip sent a step, the seconds
    their ops and transfers took (union), and the GB/s of each chip."""
    key = ("train", path, tuple(device_ids), steps)
    if key in _cache:
        return _cache[key]
    space = _load(path)
    _, lo, hi = _window(space)
    names = op_names(path, {f"/device:TPU:{d}" for d in device_ids})
    ms = collections.Counter()
    top = {c: collections.Counter() for c in CLASSES}
    link = []
    chips = 0
    for dev in device_ids:
        plane = space.find_plane_with_name(f"/device:TPU:{dev}")
        line = xtrace._line(plane, "XLA Ops") if plane is not None else None
        if line is None:
            raise ValueError(f"the trace holds no XLA Ops of TPU {dev}")
        chips += 1
        ops = names.get(f"/device:TPU:{dev}", {})
        evs = _op_events(line, lo, hi, ops)
        sent, c_iv = 0, []
        for op, name, t in self_times(evs):
            cls = classify(op)
            ms[cls] += t * 1e-6
            top[cls][xtrace.op_label(name)] += t * 1e-6
        for name, s, e, op in evs:
            base = xtrace.op_base(name)
            if base.startswith("collective-permute") and \
                    classify(op) == "gossip":
                c_iv.append((s, e))
                # what the chip sent is the result of a synchronous
                # permute or of an asynchronous one's done half (a
                # start's result holds its operand as well)
                if base in ("collective-permute", "collective-permute-done"):
                    sent += result_bytes(name)
        async_line = xtrace._line(plane, "Async XLA Ops")
        for name, s, e, op in (_op_events(async_line, lo, hi, ops)
                               if async_line is not None else []):
            if xtrace.op_base(name).startswith("collective-permute") and \
                    classify(op) in ("gossip", "unscoped"):
                c_iv.append((s, e))
        seconds = xtrace.total(xtrace.union(c_iv)) * 1e-9 if sent else 0.0
        link.append({"bytes": sent / steps, "seconds": seconds / steps,
                     "gbps": sent / seconds / 1e9 if sent else None})
    out = {"ms": {c: ms[c] / chips / steps for c in CLASSES},
           "top": {c: [[n, v / chips / steps]
                       for n, v in top[c].most_common(10)]
                   for c in CLASSES},
           "link": link, "steps": steps, "chips": chips}
    _cache[key] = out
    return out


def serve(path: str, device_id) -> dict:
    """The device's idle time in the traced part against the engine's
    spans: idle inside ``serve.sync`` and inside ``serve.step``, and the
    spans' counts."""
    key = ("serve", path, device_id)
    if key in _cache:
        return _cache[key]
    space = _load(path)
    spans, lo, hi = _window(space)
    plane = space.find_plane_with_name(f"/device:TPU:{device_id}")
    line = xtrace._line(plane, "XLA Ops") if plane is not None else None
    if line is None:
        raise ValueError(f"the trace holds no XLA Ops of TPU {device_id}")
    busy = xtrace.union([(s, e) for _, s, e in xtrace._events(line, lo, hi)])
    idle = xtrace.subtract([[lo, hi]], busy)

    def within(name):
        return [(max(s, lo), min(e, hi)) for n, s, e in spans
                if n == name and e > lo and s < hi]

    def idle_in(intervals):
        return xtrace.subtract(xtrace.union(intervals), busy)

    step, sync = within("serve.step"), within("serve.sync")
    out = {"window_s": (hi - lo) * 1e-9, "idle_s": idle * 1e-9,
           "steps": len(step), "syncs": len(sync),
           "idle_in_step_s": idle_in(step) * 1e-9,
           "idle_in_sync_s": idle_in(sync) * 1e-9}
    _cache[key] = out
    return out


def train_ms(ctx, cls: str):
    """A train metric's reading: ms a traced step of class ``cls``, or
    None for a program whose step carries no scopes."""
    if ctx["kind"] != "train" or not ctx["traced_steps"]:
        return None
    ms = train(ctx["trace_path"], ctx["devices"], ctx["traced_steps"])["ms"]
    if not (ms["forward"] or ms["update"]):
        return None
    return ms[cls]
