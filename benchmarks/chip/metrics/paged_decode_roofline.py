"""The paged decode attention kernel's share of its roofline: per call,
the K and V rows up to each slot's valid length plus q and out over the
HBM bandwidth (or its FLOPs over the peak, where larger), against the
time its kernel events took in the traced part of the run.  One kernel
event is one layer of one decode step."""
from bench import cost


def read(ctx):
    if ctx["kind"] != "serve":
        return None
    k = ctx["trace"]["kernels"].get("paged_flash_attention_pallas")
    decodes = [c for c in ctx["traced_calls"] if c[0] == "decode"]
    if not k or not k["seconds"] or not decodes:
        return None
    m = ctx["model"]
    per_call = [cost.roofline_seconds(
        ctx["peak"],
        flops=cost.paged_decode_flops(c[4], heads=m["num_attention_heads"],
                                      head_dim=m["head_dim"]),
        bytes_=cost.paged_decode_bytes(c[4], heads=m["num_attention_heads"],
                                       kv_heads=m["num_key_value_heads"],
                                       head_dim=m["head_dim"], itemsize=2))
        for c in decodes]
    least = k["count"] * sum(per_call) / len(per_call)
    return 100.0 * least / k["seconds"]
