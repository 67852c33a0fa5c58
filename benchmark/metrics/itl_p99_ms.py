"""99th percentile of the gaps between consecutive output tokens of one
request, over every gap that ends inside the window."""
from benchmark import window


def read(obs, p=0.99):
    gaps = window.gaps_in_window(obs["requests"], *obs["window"])
    return 1e3 * window.percentile(gaps, p) if gaps else None
