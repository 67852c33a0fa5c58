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
# chat-short-sat at seed 0 by the generator of PR 47 (before lanes)
GOLDEN = [(57, 80), (87, 68), (73, 84), (45, 72), (99, 76), (67, 112)]


def lengths_of(mix, seed, seconds=20):
    if mix["kind"] == "closed":
        plan = traffic.ClosedPlan(mix, seed, vocab=1000)
        reqs = [plan.take(k % mix["clients"])
                for k in range(2 * mix["count"])]
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
            return traffic.ClosedPlan(mix, seed, 1000).take(0)["tokens"]
        return traffic.open_arrivals(mix, seed, 5, 1000)[0]["tokens"]

    assert first(3) == first(3)
    assert first(3) != first(4)


# ------------------------------------------------ a closed loop by lanes
LANES = {n: m for n, m in SERVING.items() if m.get("order") == "lanes"}


def sent_by_caller(mix, seed, rounds):
    plan = traffic.ClosedPlan(mix, seed, vocab=1000)
    sent = {c: [] for c in range(mix["clients"])}
    for _ in range(rounds):
        for c in reversed(range(mix["clients"])):   # any order of asking
            r = plan.take(c)
            sent[c].append((len(r["tokens"]), r["max_new_tokens"]))
    return sent


def test_the_video_mix_goes_by_lanes():
    assert "video-32k-sat" in LANES and "tiny-lanes" in LANES


@pytest.mark.parametrize("seed", SEEDS)
@pytest.mark.parametrize("name", sorted(LANES))
def test_by_lanes_every_caller_and_every_instant_hold_the_whole_grid(name,
                                                                     seed):
    mix = LANES[name]
    n, clients = mix["count"], mix["clients"]
    grid = collections.Counter(traffic.length_pairs(mix, n))
    sent = sent_by_caller(mix, seed, 2 * n)
    for mine in sent.values():
        # a caller's any n consecutive requests are the grid
        assert all(collections.Counter(mine[k:k + n]) == grid
                   for k in range(n))
    for k in range(2 * n):
        # the k-th requests of the callers: distinct, evenly over the walk
        now = [sent[c][k] for c in range(clients)]
        assert len(set(now)) == clients
        if clients == n:
            assert collections.Counter(now) == grid


@pytest.mark.parametrize("name", sorted(LANES))
def test_by_lanes_a_seed_deals_the_places_and_never_changes_the_walk(name):
    mix = LANES[name]
    n = mix["count"]
    walks = []
    for seed in SEEDS:
        sent = sent_by_caller(mix, seed, n)
        firsts = [sent[c][0] for c in sorted(sent)]
        walks.append(firsts)
        # every caller's walk is a rotation of caller 0's
        base = sent[0] + sent[0]
        for mine in sent.values():
            at = base.index(mine[0])
            assert base[at:at + n] == mine
    assert len({tuple(w) for w in walks}) > 1      # the places: the seed's
    # and the walk: the same under every seed, consecutive points far apart
    pairs = traffic.length_pairs(mix, n)
    mine = sent_by_caller(mix, SEEDS[0], n)[0]
    at = pairs.index(mine[0])
    stride = traffic.coprime_stride(n, 0.382)
    assert mine == [pairs[(at + k * stride) % n] for k in range(n)]


def test_a_shuffled_deck_ignores_who_asks_and_draws_what_it_drew_before():
    """Every mix but the lanes' keeps its deck: the same numbers whether or
    not the caller says who it is, and for ``chat-short-sat`` at seed 0 the
    lengths the generator gave before it knew of lanes."""
    mix = SERVING["chat-short-sat"]
    a = traffic.ClosedPlan(mix, 0, 1000)
    b = traffic.ClosedPlan(mix, 0, 1000)
    for k in range(3 * mix["count"]):
        assert a.take(0) == b.take(k % mix["clients"])
    plan = traffic.ClosedPlan(mix, 0, 1000)
    first = [plan.take(0) for _ in range(6)]
    assert [(len(r["tokens"]), r["max_new_tokens"]) for r in first] == GOLDEN


def test_an_unknown_order_is_refused():
    with pytest.raises(ValueError, match="order"):
        traffic.ClosedPlan({**SERVING["tiny-closed"], "order": "sorted"},
                           0, 1000)


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


# ------------------- a closed deck of which a window sees a part, in strata
# the two decks ISSUE 62 names, with the key whether or not their files have
# it (the chip decides that: PERF.md section 2), and the stretches a cycle has
DECKS = {name: ({**SERVING[name], "order_block": 64}, stretches)
         for name, stretches in (("agent-steps-sat", 32),
                                 ("reason-short-sat", 16))}


def dealt(mix, seed, cycles=1):
    plan = traffic.ClosedPlan(mix, seed, vocab=1000)
    return [(len(r["tokens"]), r["max_new_tokens"])
            for r in (plan.take(k % mix["clients"])
                      for k in range(cycles * mix["count"]))]


@pytest.mark.parametrize("name", sorted(DECKS))
def test_a_stratified_closed_deck_deals_every_pair_once_a_cycle(name):
    mix, _ = DECKS[name]
    pairs, n = sorted(traffic.length_pairs(mix, mix["count"])), mix["count"]
    two = dealt(mix, 5, cycles=2)
    assert sorted(two[:n]) == pairs and sorted(two[n:]) == pairs
    assert two[:n] != two[n:]              # a fresh order each cycle


@pytest.mark.parametrize("name", sorted(DECKS))
def test_every_stretch_of_a_closed_deck_holds_each_length_class_once(name):
    """A stretch of ``order_block`` requests holds one prompt of each of 64
    classes of neighbouring lengths, so its sorted prompts lie class by class
    inside the grid's (ties between neighbouring classes fit both), and its
    prompt tokens stand within a few per cent of any other stretch's, where
    a plain shuffle's stretches stand tens of per cent apart."""
    mix, stretches = DECKS[name]
    grid = [p for p, _ in traffic.length_pairs(mix, mix["count"])]
    assert grid == sorted(grid) and len(grid) == 64 * stretches
    classes = [grid[k * stretches:(k + 1) * stretches] for k in range(64)]
    for seed in SEEDS:
        prompts = [p for p, _ in dealt(mix, seed)]
        sums = []
        for j in range(stretches):
            stretch = sorted(prompts[64 * j:64 * (j + 1)])
            assert all(c[0] <= p <= c[-1] for p, c in zip(stretch, classes))
            sums.append(sum(stretch))
        plain = [p for p, _ in dealt({**mix, "order_block": None}, seed)]
        plain = [sum(plain[64 * j:64 * (j + 1)]) for j in range(stretches)]
        assert max(sums) - min(sums) < 0.5 * (max(plain) - min(plain))


@pytest.mark.parametrize("name", sorted(DECKS))
def test_two_seeds_of_a_stratified_closed_deck_offer_the_same_multiset(name):
    mix, _ = DECKS[name]
    a, b = dealt(mix, SEEDS[1], 2), dealt(mix, SEEDS[2], 2)
    assert collections.Counter(a) == collections.Counter(b) and a != b
    # and the deck does not look at who asks, as every shuffled deck
    one = traffic.ClosedPlan(mix, 3, 1000)
    assert [(len(r["tokens"]), r["max_new_tokens"])
            for r in (one.take(0) for _ in range(200))] == dealt(mix, 3)[:200]


@pytest.mark.parametrize("block", [None, 0, 4096])
def test_a_closed_deck_without_the_key_draws_what_it_drew(block):
    """No ``order_block`` (or one no smaller than the deck): a cycle is the
    plain permutation the generator drew before PR 62, request for request
    (``chat-short-sat``'s golden list above holds the same by numbers)."""
    import numpy as np

    mix = {k: v for k, v in SERVING["agent-steps-sat"].items()
           if k != "order_block"}
    if block is not None:
        mix["order_block"] = block
    pairs = traffic.length_pairs(mix, mix["count"])
    rng = np.random.default_rng(9)
    order = rng.permutation(len(pairs))
    want = [traffic._request(rng, 1000, *pairs[i]) for i in order[::-1]][::-1]
    plan = traffic.ClosedPlan(mix, 9, 1000)
    assert [plan.take(0) for _ in range(300)] == want[:300]


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
