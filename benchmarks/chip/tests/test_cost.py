"""FLOP and byte arithmetic and the peaks table."""
import json
import sys

import pytest

from conftest import HERE

from bench import cost, peaks, spec

dense = spec.load_arch("dense_decoder")


def config(name):
    return json.loads((HERE / "configs" / f"{name}.json").read_text())


def test_train_step_flops_match_hand_count():
    # granite-8b at 2 blocks, one node, 2 x 2048 tokens a step
    tokens, t = 4096, 2048
    d, h, kv, hd, f, v = 4096, 32, 8, 128, 14336, 49152
    attn = (2 * tokens * d * (h + 2 * kv) * hd          # q, k, v
            + 4 * 2 * (t * (t + 1) / 2) * h * hd        # causal scores + PV
            + 2 * tokens * h * hd * d)                  # out
    ffn = 6 * tokens * d * f
    head = 2 * tokens * d * v
    want = 3 * (2 * (attn + ffn) + head)
    got = cost.train_step_flops(dense, config("granite-8b-2blk"),
                                sequences=2, seq=t)
    assert got == pytest.approx(want, rel=1e-12)
    assert got == pytest.approx(16.08e12, rel=2e-3)


def test_serve_flops_match_hand_count():
    # qwen1.5-4b whole, as serve_mfu counts a token: a decode token that
    # attends to 700 keys, and a prefill of 512
    m = config("qwen1.5-4b")
    assert m["reference"] == "dense_decoder"
    d, h, hd, f, v, layers = 2560, 20, 128, 6912, 151936, 40
    per_token = layers * (2 * d * 3 * h * hd + 2 * h * hd * d + 6 * d * f) \
        + 2 * d * v
    per_pair = layers * 4 * h * hd
    assert per_token == 7_121_797_120
    assert dense.forward_flops(m, tokens=1, attended=700) == \
        per_token + 700 * per_pair
    assert dense.forward_flops(m, tokens=512, attended=cost.causal_pairs(
        512)) == 512 * per_token + 512 * 513 / 2 * per_pair


def test_kernel_bytes_match_the_stream_model():
    sys.path.insert(0, str(HERE.parents[1]))
    from benchmarks.kernels import dsgd_streams, gossip_streams
    n = 4096 * 14336
    assert cost.dsgd_update_bytes(n, x_itemsize=2, u_itemsize=2,
                                  g_itemsize=2) == dsgd_streams()["fused"] * n * 2
    for s in (1, 2, 4, 8):
        assert cost.gossip_mix_bytes(n, slots=s) == \
            gossip_streams(s)["fused"] * n * 4


def test_flash_flops_count_causal_pairs():
    full = cost.flash_fwd_flops(batch=1, heads=1, head_dim=1, q_len=8,
                                k_len=8, causal=False)
    causal = cost.flash_fwd_flops(batch=1, heads=1, head_dim=1, q_len=8,
                                  k_len=8, causal=True)
    assert full == 4 * 64 and causal == 4 * 36
    # a decode row against a cache of 8 sees all 8 keys
    assert cost.flash_fwd_flops(batch=1, heads=1, head_dim=1, q_len=1,
                                k_len=8, causal=True) == 4 * 8


def test_roofline_takes_the_larger_bound():
    p = peaks.peak_for("TPU v5 lite")
    assert cost.roofline_seconds(p, flops=197e12, bytes_=0) == 1.0
    assert cost.roofline_seconds(p, flops=0, bytes_=819e9) == 1.0
    assert cost.roofline_seconds(p, flops=197e12, bytes_=2 * 819e9) == 2.0


def test_unknown_device_kind_raises():
    with pytest.raises(ValueError, match="no published peaks"):
        peaks.peak_for("TPU v9 imaginary")
