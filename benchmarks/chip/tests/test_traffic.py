"""The generators give the same inputs for the same seed, and other
inputs for another; every seed of a serving mix carries the same work."""
import json

import numpy as np

from conftest import HERE

from bench import traffic

SERVE = json.loads((HERE / "traffic" / "serve-saturated.json").read_text())
TRAIN = json.loads((HERE / "traffic" / "train-base2-4node.json").read_text())
BIG = 2**33 + 17


def test_node_batch_is_seeded():
    mix = dict(TRAIN, seq=64)
    a = traffic.node_batch(3, mix, 49152, BIG)
    b = traffic.node_batch(3, mix, 49152, BIG)
    c = traffic.node_batch(3, mix, 49152, BIG + 1)
    d = traffic.node_batch(4, mix, 49152, BIG)
    assert a["tokens"].shape == (4, 2, 64)
    np.testing.assert_array_equal(a["tokens"], b["tokens"])
    assert not np.array_equal(a["tokens"], c["tokens"])
    assert not np.array_equal(a["tokens"], d["tokens"])
    assert (a["labels"][..., -1] == -100).all()
    np.testing.assert_array_equal(a["labels"][..., :-1], a["tokens"][..., 1:])


def test_token_batches_matches_the_program_generator():
    from repro.data.synthetic import token_batches
    for step in (0, 5):
        want = token_batches(step, batch=4, seq=32, vocab=1000, seed=BIG)
        got = traffic.token_batches(step, batch=4, seq=32, vocab=1000,
                                    seed=BIG)
        for k in want:
            np.testing.assert_array_equal(want[k], got[k])


def test_serve_requests_are_seeded_and_carry_the_same_work():
    a = traffic.serve_requests(SERVE, BIG, 151936)
    b = traffic.serve_requests(SERVE, BIG, 151936)
    c = traffic.serve_requests(SERVE, 7, 151936)
    assert a == b
    assert a != c
    # another seed draws other tokens for the same lengths at the same steps
    assert [len(t) for _, t, _ in a] == [len(t) for _, t, _ in c]
    assert [x for _, _, x in a] == [x for _, _, x in c]
    lens = [len(t) for _, t, _ in a]
    p = SERVE["prompt"]
    assert p["min"] <= min(lens) and max(lens) <= p["max"]
    assert 400 <= np.median(lens) <= 620
    arrivals = np.array([x for _, _, x in a])
    assert arrivals[0] == 0 and (np.diff(arrivals) >= 0).all()
    rate = (len(a) - 1) / arrivals[-1]
    assert abs(rate / SERVE["rate_per_step"] - 1) < 0.15
