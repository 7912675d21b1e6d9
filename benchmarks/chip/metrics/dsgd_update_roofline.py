"""The fused DSGD-momentum update's share of its roofline: the bytes it
must move (read x, u, g; write x', u' -- 5 streams a leaf) over the HBM
bandwidth, against the time its kernel events took in the trace."""
from bench import cost


def read(ctx):
    if ctx["kind"] != "train":
        return None
    k = ctx["trace"]["kernels"].get("fused_dsgd_pallas")
    leaves = ctx["param_leaves"]
    if not k or not k["seconds"]:
        return None
    node_bytes = sum(cost.dsgd_update_bytes(n, x_itemsize=s, u_itemsize=s,
                                            g_itemsize=s)
                     for n, s in leaves)
    calls_per_node_step = len(leaves)
    steps = k["count"] / calls_per_node_step
    least = steps * node_bytes / ctx["peak"].hbm_bytes_per_s
    return 100.0 * least / k["seconds"]
