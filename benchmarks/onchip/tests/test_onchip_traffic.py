"""Traffic generators: deterministic per seed, inside their clips, and
offering the same work on every seed."""
import sys
from collections import Counter
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from harness import spec, traffic  # noqa: E402

BATCH = spec.load_traffic("batch_closed")
BIG_SEED = 2 ** 31 + 977
SLOTS = 32


def _gen(seed, slots=SLOTS):
    return traffic.make(BATCH, seed, vocab=50280, slots=slots)


def _serve(seed, n=200):
    """The first wave, then ``n`` requests sent as callers finish in
    turn."""
    gen = _gen(seed)
    out = gen.due(gen.start)
    for k in range(n):
        gen.finished(out[k][1], float(k))
        out += gen.due(float(k))
    return [i for _, i in out]


@pytest.mark.parametrize("slots", [4, SLOTS])
def test_closed_loop_clients_send_on_finish(slots):
    gen = _gen(BIG_SEED, slots)
    first = gen.due(gen.start)
    n = BATCH["clients_per_slot"] * slots
    assert len(first) == n
    assert sorted(i.client for _, i in first) == list(range(n))
    assert all(i.warm for _, i in first)
    assert gen.due(5.0) == []
    fw = BATCH["first_wave_output"]
    assert all(fw["min"] <= i.max_new <= fw["max"] for _, i in first)
    gen.finished(first[3][1], 1.5)
    again = gen.due(1.5)
    assert len(again) == 1 and again[0][1].client == first[3][1].client
    assert again[0][0] == 1.5 and not again[0][1].warm
    assert gen.next_time() is None
    gen.stop()
    gen.finished(again[0][1], 2.0)
    assert gen.due(1e9) == []


@pytest.mark.parametrize("seed", [0, 1, BIG_SEED])
def test_closed_loop_inside_clips(seed):
    items = _serve(seed)
    p, o = BATCH["prompt"], BATCH["output"]
    n = BATCH["clients_per_slot"] * SLOTS
    for i in items[n:]:
        assert p["min"] <= len(i.prompt) <= p["max"]
        assert o["min"] <= i.max_new <= o["max"]
        assert i.prompt.min() >= 1 and i.prompt.max() < 50280
        assert i.greedy and i.top_p == 1.0 and i.top_k == 0
    assert len({i.rid for i in items}) == len(items)


def test_closed_loop_is_deterministic_per_seed():
    def seq(seed):
        return [(i.rid, len(i.prompt), i.max_new, i.prompt[:4].tolist())
                for i in _serve(seed, 100)]
    assert seq(BIG_SEED) == seq(BIG_SEED)
    assert seq(11) != seq(12)


def test_closed_loop_seeds_offer_the_same_work_in_another_order():
    """Each block of one request per caller holds the same sizes on every
    seed."""
    n = BATCH["clients_per_slot"] * SLOTS
    a, b = _serve(7, 2 * n), _serve(8, 2 * n)
    for block in (slice(0, n), slice(n, 2 * n), slice(2 * n, 3 * n)):
        for key in (lambda i: len(i.prompt), lambda i: i.max_new):
            assert Counter(map(key, a[block])) == Counter(map(key, b[block]))
    assert [len(i.prompt) for i in a] != [len(i.prompt) for i in b]


def test_unknown_kind_and_distribution_are_refused():
    with pytest.raises(KeyError, match="unknown kind"):
        traffic.make({"kind": "nope", "name": "x"}, 0, 10, 1)
    with pytest.raises(KeyError, match="unknown distribution"):
        traffic.quantile({"dist": "zipf"}, 0.5)
