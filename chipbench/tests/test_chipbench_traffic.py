"""The traffic generator: deterministic per seed, lengths rounded and
clipped as the mix says, the offered rate exact, and the same work for
every seed in another order."""
import _paths  # noqa: F401
import numpy as np
import pytest

from chipbench import spec, traffic_gen

CHAT = spec.load_json(spec.traffic_path("chat"))
BIG = 2**31 + 12345


def _sched(seed, rate=5.0, span=40.0):
    return traffic_gen.schedule(CHAT, rate, span, 151936, seed)


def test_same_seed_same_schedule():
    a, b = _sched(BIG), _sched(BIG)
    assert [(x.due_s, x.out_len) for x in a] == \
        [(x.due_s, x.out_len) for x in b]
    assert all(np.array_equal(x.prompt, y.prompt) for x, y in zip(a, b))
    c = _sched(BIG + 1)
    assert [x.due_s for x in a] != [x.due_s for x in c]


def test_lengths_rounded_and_clipped():
    reqs = _sched(7, rate=20.0, span=60.0)
    plens = np.array([len(x.prompt) for x in reqs])
    outs = np.array([x.out_len for x in reqs])
    assert (plens % 256 == 0).all()
    assert plens.min() >= 256 and plens.max() <= 3072
    assert set(plens) == set(traffic_gen.prompt_buckets(CHAT))
    assert outs.min() >= 8 and outs.max() <= 1023
    # stratified: the medians are the mix's (before rounding)
    assert abs(np.median(outs) - 129) <= 2
    assert 1020 <= np.median(plens) <= 1024
    toks = np.concatenate([x.prompt for x in reqs])
    assert toks.min() >= 0 and toks.max() < 151936


def test_rate_is_exact_and_arrivals_in_span():
    reqs = _sched(3, rate=4.0, span=50.0)
    assert len(reqs) == 200
    due = np.array([x.due_s for x in reqs])
    assert (np.diff(due) > 0).all() and 0 < due[0] and due[-1] < 50.0
    assert abs(np.mean(np.diff(due)) - 0.25) < 0.01


def test_lengths_are_balanced_through_the_window():
    reqs = _sched(5, rate=4.0, span=50.0)
    outs = np.array([x.out_len for x in reqs])
    ranks = np.argsort(np.argsort(outs, kind="stable"), kind="stable")
    block = traffic_gen.BLOCK
    for i in range(0, len(outs) - block + 1, block):
        strata = sorted(ranks[i:i + block] * block // len(outs))
        assert strata == list(range(block))
    order = traffic_gen.balanced_order(20, np.random.default_rng(1))
    assert sorted(order) == list(range(20))


def test_every_seed_gets_the_same_work():
    a, b = _sched(11), _sched(2**33 + 5)
    assert sorted(len(x.prompt) for x in a) == sorted(len(x.prompt)
                                                      for x in b)
    assert sorted(x.out_len for x in a) == sorted(x.out_len for x in b)
    gaps = lambda s: sorted(np.round(np.diff([0.0] + [x.due_s for x in s]),
                                     9))
    assert len(gaps(a)) == len(gaps(b))


@pytest.mark.parametrize("key,kind", [("arrivals", "poisson"),
                                      ("lengths", "lognormal_iid")])
def test_the_mix_names_its_draw(key, kind):
    """The stratified draw is named in the mix; a mix asking for another
    one is refused, not served stratified under another name."""
    assert CHAT["arrivals"] == "poisson_stratified"
    assert CHAT["lengths"] == "lognormal_stratified_balanced"
    with pytest.raises(ValueError):
        traffic_gen.schedule({**CHAT, key: kind}, 1.0, 10.0, 100, 1)
