"""95th percentile of how late the clients sent each request after its
due time: a starved generator must not read as a fast server."""
from bench.gen import percentile


def read(run):
    late = [(r["sent"] - r["due"]) * 1e3 for r in run.window_requests()]
    return percentile(late, 95) if late else None
