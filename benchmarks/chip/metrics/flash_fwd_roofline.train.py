"""The flash-attention forward kernel's share of its roofline in the
train step: causal FLOPs (and q, k, v, out bytes) from the call's shape
over the bf16 peak (and the HBM bandwidth), against the time its kernel
events took.  The remat recompute runs the kernel again; each run is a
call and counts."""
from bench import cost


def read(ctx):
    if ctx["kind"] != "train":
        return None
    k = ctx["trace"]["kernels"].get("flash_attention_pallas")
    if not k or not k["seconds"]:
        return None
    m, seq, rows = ctx["model"], ctx["mix"]["seq"], ctx["mix"]["rows_per_node"]
    flops = cost.flash_fwd_flops(batch=rows, heads=m["num_attention_heads"],
                                 head_dim=m["head_dim"], q_len=seq,
                                 k_len=seq, causal=True)
    bytes_ = cost.flash_fwd_bytes(batch=rows, heads=m["num_attention_heads"],
                                  kv_heads=m["num_key_value_heads"],
                                  head_dim=m["head_dim"], q_len=seq,
                                  k_len=seq, itemsize=2)
    least = k["count"] * cost.roofline_seconds(ctx["peak"], flops=flops,
                                               bytes_=bytes_)
    return 100.0 * least / k["seconds"]
