"""95th percentile of how late the clients sent each request after its
due time, in the classify cell, where it moves the median latency (that
cell's 95th percentile is too unsteady for a bound)."""
from bench.gen import percentile


def read(run):
    late = [(r["sent"] - r["due"]) * 1e3 for r in run.window_requests()]
    return percentile(late, 95) if late else None
