"""90th percentile of first-token time, from the instant each request was
DUE, over the requests due inside the window; a failed, shed or evicted
request is infinitely late."""
from benchmark import window


def read(obs, p=0.9):
    ttfts = window.ttfts_from_due(obs["requests"], *obs["window"])
    return 1e3 * window.percentile(ttfts, p) if ttfts else None
