"""Median client-side time from each request's due time to its reply, over
every request due in the window; one that never came back counts as
missing (infinite)."""
from bench.gen import percentile


def read(run):
    lat = [(r["done"] - r["due"]) * 1e3 for r in run.window_requests()]
    return percentile([x if x == x else float("inf") for x in lat], 50)
