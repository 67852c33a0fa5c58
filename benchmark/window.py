"""Window arithmetic: from the harness's own records of a run to numbers.

A request's record is a dict the load loop fills on the harness's clock
(``time.perf_counter``): ``due`` (when it should be sent), ``sent`` (when
it was), ``emits`` (the delivery time of each output token, in order),
``budget`` (its ``max_new_tokens``) and ``closed`` (the finish reason, or
``None``). A rate or a tail is taken over ALL the work and ALL the time of
the window; nothing here drops, trims or smooths a sample. A token belongs
to the window ``(t0, t1]`` by its delivery time (the closed loop's window
opens and closes at round returns: the opening round's tokens came before
it, the closing round's inside it); a request belongs to ``[t0, t1)`` by its
due time.
"""
import math

MIN_BEYOND = 10   # a percentile stands on at least ten samples beyond it


def ok(request):
    """Closed ``done`` with every token of its budget."""
    return (request["closed"] == "done"
            and len(request["emits"]) == request["budget"])


def tokens_in_window(requests, t0, t1):
    """Output tokens whose delivery time falls inside the window — a
    token counts where it is emitted, whether or not its request has
    finished by ``t1``."""
    return sum(1 for r in requests for t in r["emits"] if t0 < t <= t1)


def gaps_in_window(requests, t0, t1):
    """Every gap between consecutive output tokens of one request whose
    later token falls inside the window, in seconds."""
    return [b - a for r in requests
            for a, b in zip(r["emits"], r["emits"][1:]) if t0 < b <= t1]


def ttfts_from_due(requests, t0, t1):
    """First-token time of every request DUE inside the window, counted
    from its due time (so a stalled generator's lateness is the request's
    wait, not hidden). A request that failed, was shed, closed ``evicted``
    with part of its output, or never emitted, misses any limit: infinity.
    (One that was evicted, prefilled again under the ``requeue`` policy and
    closed ``done`` counts with the time its first token really took.)"""
    return [(r["emits"][0] - r["due"]) if ok(r) else math.inf
            for r in requests if t0 <= r["due"] < t1]


def percentile(values, p):
    """Nearest-rank ``p``-quantile (0 < p <= 1): the smallest sample with
    at least ``p`` of all samples at or below it."""
    if not values:
        raise ValueError("percentile of no samples")
    ordered = sorted(values)
    return ordered[max(0, math.ceil(p * len(ordered)) - 1)]


def beyond(n, p):
    """How many of ``n`` samples lie strictly beyond the ``p``-quantile."""
    return n - math.ceil(p * n)


def supported(n, p):
    """Whether ``n`` samples carry the ``p``-quantile: ten beyond it."""
    return beyond(n, p) >= MIN_BEYOND


def highest_supported(n, candidates=(0.5, 0.9, 0.95, 0.99, 0.999)):
    """The highest of ``candidates`` that ``n`` samples support (``None``
    below 20 samples, where not even the median has ten beyond it)."""
    good = [p for p in candidates if supported(n, p)]
    return max(good) if good else None


def late(requests, t0, t1):
    """How late the generator sent each request due in the window."""
    return [r["sent"] - r["due"] for r in requests
            if t0 <= r["due"] < t1 and r["sent"] is not None]
