"""A seed permutes and phases the work; it does not resample it."""
import collections

import pytest

from benchmark import spec, traffic

from . import tiny

BENCH = spec.Bench()
SERVING = {w["traffic"]: BENCH.traffic(w["traffic"])
           for w in BENCH.doc["workloads"]
           if BENCH.traffic(w["traffic"])["kind"] != "train-batches"}
SERVING.update({n: m for n, m in tiny.TRAFFIC.items()
                if m["kind"] != "train-batches"})
SEEDS = (0, 7, 2**31 + 11)


def lengths_of(mix, seed, seconds=20):
    if mix["kind"] == "closed":
        plan = traffic.ClosedPlan(mix, seed, vocab=1000)
        reqs = [plan.take() for _ in range(2 * mix["count"])]
    else:
        reqs = [a for a in traffic.open_arrivals(mix, seed, seconds, 1000)
                if 0 <= a["due"]]
    return [(len(r["tokens"]), r["max_new_tokens"]) for r in reqs]


@pytest.mark.parametrize("name", sorted(SERVING))
def test_every_seed_offers_the_same_multiset_of_lengths(name):
    mix = SERVING[name]
    sets = [collections.Counter(lengths_of(mix, s)) for s in SEEDS]
    assert sets[0] == sets[1] == sets[2]
    orders = [lengths_of(mix, s) for s in SEEDS]
    assert orders[0] != orders[1]          # ... in another order


@pytest.mark.parametrize("name", sorted(SERVING))
def test_every_seed_offers_the_same_tokens_per_second(name):
    mix = SERVING[name]
    offered = []
    for seed in SEEDS:
        pairs = lengths_of(mix, seed)
        offered.append((sum(p for p, _ in pairs), sum(o for _, o in pairs)))
    assert offered[0] == offered[1] == offered[2]
    if mix["kind"] == "open-fixed-rate":
        per_s = traffic.offered_per_s(mix, 20)
        assert per_s == (offered[0][0] / 20, offered[0][1] / 20)


@pytest.mark.parametrize("name", sorted(SERVING))
def test_token_ids_follow_the_seed(name):
    mix = SERVING[name]

    def first(seed):
        if mix["kind"] == "closed":
            return traffic.ClosedPlan(mix, seed, 1000).take()["tokens"]
        return traffic.open_arrivals(mix, seed, 5, 1000)[0]["tokens"]

    assert first(3) == first(3)
    assert first(3) != first(4)


def test_lengths_stay_inside_the_stated_range_and_quantiles_are_ordered():
    mix = BENCH.traffic("docs-long")
    pairs = traffic.length_pairs(mix, 101)
    prompts = [p for p, _ in pairs]
    assert prompts == sorted(prompts)
    assert 256 <= prompts[0] and prompts[-1] <= 1792
    assert all(16 <= o <= 64 for _, o in pairs)
    # heavy tail: the mean sits well above the median
    assert sum(prompts) / 101 > prompts[50] * 1.1
    # outputs are decorrelated from prompts by the fixed stride
    assert [o for _, o in pairs] != sorted(o for _, o in pairs)


def test_chat_short_sat_can_never_exhaust_the_pool():
    mix, cfg = BENCH.traffic("chat-short-sat"), BENCH.config("phi-2")
    eng = cfg["engine"]
    worst = max(p + o for p, o in traffic.length_pairs(mix, mix["count"]))
    blocks = -(-worst // eng["block_size"])
    assert mix["clients"] == eng["max_sequences"]
    assert mix["clients"] * blocks <= eng["num_blocks"]


def test_open_arrivals_are_one_per_gap_with_at_most_half_a_gap_of_jitter():
    mix = {**BENCH.traffic("docs-long"), "rate_per_s": 2.0}
    arrivals = traffic.open_arrivals(mix, 5, seconds=10, vocab=100)
    window = [a["due"] for a in arrivals if a["due"] >= 0]
    assert len(window) == 20 and window == sorted(window)
    for slot, due in enumerate(window):
        assert slot * 0.5 <= due <= (slot + 1) * 0.5
    ramp = [a["due"] for a in arrivals if a["due"] < 0]
    assert len(ramp) == round(mix["ramp_seconds"] * 2.0)
    assert min(ramp) >= -mix["ramp_seconds"]
    with pytest.raises(ValueError, match="jitter"):
        traffic.open_arrivals({**mix, "jitter_gaps": 0.7}, 0, 10, 100)


def test_stratified_order_spreads_every_length_class_over_the_window():
    mix = BENCH.traffic("docs-long")
    assert mix["order_block"] == 10 and mix["jitter_gaps"] <= 0.5
    for seed in SEEDS:
        lens = [len(a["tokens"]) for a in
                traffic.open_arrivals(mix, seed, 51, 100) if a["due"] >= 0]
        assert len(lens) == 102
        top = sorted(lens)[-18:-2]         # two whole classes of long prompts
        where = [i for i, n in enumerate(lens) if n in top]
        # never a stretch of the window without a long prompt
        assert max(b - a for a, b in zip(where, where[1:])) <= 21


def test_each_stretch_holds_one_request_of_each_length_class():
    import numpy as np

    order = traffic.spread_order(np.random.default_rng(4), 100, block=10)
    for k in range(10):
        stretch = order[10 * k:10 * k + 10]
        assert sorted(i // 10 for i in stretch) == list(range(10))
    assert order != traffic.spread_order(np.random.default_rng(5), 100, 10)


def test_spread_order_is_a_permutation_with_and_without_blocks():
    import numpy as np

    for block in (None, 3, 10, 500):
        order = traffic.spread_order(np.random.default_rng(1), 37, block)
        assert sorted(order) == list(range(37))


def test_a_traced_run_gets_a_tail_of_arrivals_after_the_window():
    mix = {**BENCH.traffic("docs-long"), "rate_per_s": 2.0}
    base = traffic.open_arrivals(mix, 5, 10, 100)
    more = traffic.open_arrivals(mix, 5, 10, 100, extra_seconds=3)
    assert more[:len(base)] == base
    assert [a["due"] >= 10 for a in more[len(base):]] == [True] * 6


def test_pattern_batches_are_fresh_seeded_and_learnable():
    a = traffic.pattern_batches(1, 2, 16, 400)
    b = traffic.pattern_batches(1, 2, 16, 400)
    first, second = next(a), next(a)
    assert (first["input_ids"] == next(b)["input_ids"]).all()
    assert (first["input_ids"] != second["input_ids"]).any()
    ids = first["input_ids"]
    assert ids.shape == (2, 16) and ids.max() < 100
    step = (ids[:, 1:] - ids[:, :-1]) % 100
    assert (step == step[:, :1]).all()     # one stride per row
