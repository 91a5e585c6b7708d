"""Seeded traffic: the same seed gives the same requests, another seed
another order of the same work."""

import numpy as np
import pytest

from bench import traffic

# an open loop of chat: Poisson arrivals, heavy-tailed lengths
CHAT = {"loop": "open",
        "prompt": {"dist": "lognormal", "median": 256, "sigma": 0.8,
                   "min": 16, "max": 512},
        "output": {"dist": "lognormal", "median": 128, "sigma": 0.6,
                   "min": 8, "max": 255}}
MIXES = {"chat": CHAT, "rag": traffic.load_mix("rag")}


def _open(seed):
    return traffic.open_loop(CHAT, seed, rate=2.0, start=-5.0, end=30.0,
                             vocab=32064)


def _closed(seed):
    return traffic.closed_loop(MIXES["rag"], seed, clients=8, per_client=5,
                               vocab=49152)


def _flat(queues):
    return [it for q in queues for it in q]


@pytest.mark.parametrize("make", [_open, _closed], ids=["open", "closed"])
def test_same_seed_same_requests(make):
    a, b = make(2 ** 31 + 5), make(2 ** 31 + 5)
    a = a if isinstance(a[0], traffic.Item) else _flat(a)
    b = b if isinstance(b[0], traffic.Item) else _flat(b)
    assert [(i.rid, i.max_new, i.due, i.client) for i in a] == \
        [(i.rid, i.max_new, i.due, i.client) for i in b]
    assert all(np.array_equal(x.prompt, y.prompt) for x, y in zip(a, b))


@pytest.mark.parametrize("make", [_open, _closed], ids=["open", "closed"])
def test_other_seed_other_draws_same_work(make):
    a, b = make(1), make(2 ** 31 + 2)
    a = a if isinstance(a[0], traffic.Item) else _flat(a)
    b = b if isinstance(b[0], traffic.Item) else _flat(b)
    assert not all(np.array_equal(x.prompt[:4], y.prompt[:4])
                   for x, y in zip(a, b))
    # the same lengths in another order: the seed moves work, adds none
    assert sorted(len(i.prompt) for i in a) == sorted(len(i.prompt)
                                                      for i in b)
    assert sorted(i.max_new for i in a) == sorted(i.max_new for i in b)
    assert [len(i.prompt) for i in a] != [len(i.prompt) for i in b]
    assert len(set(len(i.prompt) for i in a)) > 1


def test_every_closed_loop_round_holds_the_same_lengths():
    for seed in (3, 2 ** 31 + 11):
        queues = _closed(seed)
        for n in range(5):
            rnd = [q[n] for q in queues]
            assert sorted(len(i.prompt) for i in rnd) == list(
                traffic.quantiles(MIXES["rag"]["prompt"], 8))
            assert sorted(i.max_new for i in rnd) == list(
                traffic.quantiles(MIXES["rag"]["output"], 8))


def test_open_loop_arrivals_fill_the_span_at_the_rate():
    items = _open(7)
    due = np.array([i.due for i in items])
    # the same gaps between arrivals for every seed, in another order
    other = np.array([i.due for i in _open(8)])
    gaps = lambda t: np.sort(np.diff(np.concatenate([[-5.0], t])))
    assert np.allclose(gaps(due), gaps(other))
    assert np.all(np.diff(due) > 0)
    assert due[0] >= -5.0 and len(items) == 70
    assert abs(due[-1] - 30.0) < 3.0


@pytest.mark.parametrize("mix", MIXES)
def test_lengths_stay_in_bounds(mix):
    m = MIXES[mix]
    q = traffic.quantiles(m["prompt"], 500)
    o = traffic.quantiles(m["output"], 500)
    assert q.min() >= m["prompt"]["min"] and q.max() <= m["prompt"]["max"]
    assert o.min() >= m["output"]["min"] and o.max() <= m["output"]["max"]
    assert traffic.longest(m) == m["prompt"]["max"] + m["output"]["max"]


def test_lognormal_quantiles_keep_the_median():
    m = CHAT
    assert np.median(traffic.quantiles(m["prompt"], 1001)) == 256
    assert np.median(traffic.quantiles(m["output"], 1001)) == 128


def test_closed_loop_gives_each_client_its_queue():
    queues = _closed(3)
    assert len(queues) == 8 and all(len(q) == 5 for q in queues)
    assert all(it.client == c for c, q in enumerate(queues) for it in q)
