"""The classify cell's 95th percentile of the client-side time from each
request's due time to its reply, over every request due in the window
(one that never came back counts as infinite).  It is read per layer, not
held to a bound: at about 100 ms it is of the size of the host's own
pauses, so it swings from run to run by more than a bound may allow."""
from bench.gen import percentile


def read(run):
    lat = [(r["done"] - r["due"]) * 1e3 for r in run.window_requests()]
    return percentile([x if x == x else float("inf") for x in lat], 95)
