"""The window arithmetic on hand-made event lists."""
import math

import pytest

from benchmark import window


def req(due, emits, budget=None, closed="done", sent=None):
    return {"due": due, "sent": due if sent is None else sent,
            "emits": emits, "closed": closed,
            "budget": len(emits) if budget is None else budget}


def test_tokens_count_by_their_own_emission_time_not_by_finished_requests():
    a = req(0.0, [1.0, 2.0, 3.0, 4.0])          # finishes inside
    b = req(0.0, [3.5, 4.5, 5.5])               # still running at the close
    c = req(0.0, [0.5, 1.5])                    # started before the window
    assert window.tokens_in_window([a, b, c], 1.0, 5.0) == 3 + 2 + 1
    # the opening instant's tokens came before; the closing instant's count
    assert window.tokens_in_window([a], 1.0, 4.0) == 3


def test_gaps_are_between_consecutive_tokens_of_one_request():
    a = req(0.0, [1.0, 1.1, 1.4])
    b = req(0.0, [1.05, 2.0])
    gaps = sorted(window.gaps_in_window([a, b], 1.0, 1.5))
    assert gaps == pytest.approx([0.1, 0.3])    # b's gap ends outside
    assert window.gaps_in_window([req(0, [1.2])], 1.0, 2.0) == []


def test_first_token_time_runs_from_the_due_time():
    late = req(due=1.0, emits=[1.8, 1.9], sent=1.3)
    assert window.ttfts_from_due([late], 0.0, 5.0) == pytest.approx([0.8])
    assert window.late([late], 0.0, 5.0) == pytest.approx([0.3])
    # due outside the window: not this window's request
    assert window.ttfts_from_due([late], 2.0, 5.0) == []


@pytest.mark.parametrize("broken", [
    req(1.0, [1.5], budget=4, closed="evicted"),
    req(1.0, [], budget=4, closed="shed"),
    req(1.0, [1.5, 1.6], budget=4, closed=None),
    req(1.0, [1.5, 1.6], budget=4, closed="done"),      # short of budget
])
def test_a_failed_shed_or_evicted_request_misses_any_limit(broken):
    assert not window.ok(broken)
    assert window.ttfts_from_due([broken], 0.0, 5.0) == [math.inf]
    good = [req(1.0, [1.2]) for _ in range(8)]
    assert window.percentile(
        window.ttfts_from_due(good + [broken], 0, 5), 0.9) == math.inf


def test_percentile_is_nearest_rank():
    values = list(range(1, 101))
    assert window.percentile(values, 0.5) == 50
    assert window.percentile(values, 0.9) == 90
    assert window.percentile(values, 0.99) == 99
    assert window.percentile([3.0], 0.99) == 3.0
    with pytest.raises(ValueError):
        window.percentile([], 0.5)


@pytest.mark.parametrize("n,p,expect", [(100, 0.9, True), (99, 0.9, False),
                                        (1000, 0.99, True), (999, 0.99, False),
                                        (20, 0.5, True), (19, 0.5, False)])
def test_a_percentile_needs_ten_samples_beyond_it(n, p, expect):
    assert window.supported(n, p) is expect
    assert window.beyond(n, p) == n - math.ceil(p * n)


def test_the_highest_percentile_a_sample_supports():
    assert window.highest_supported(15) is None
    assert window.highest_supported(150) == 0.9
    assert window.highest_supported(17000) == 0.999
