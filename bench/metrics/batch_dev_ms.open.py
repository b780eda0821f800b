"""Device time of ``jit(prefill)`` and every ``jit(decode)`` of one
batch, averaged over the batches that ran wholly inside the traced
window."""


def read(run):
    batches = run.complete_batches()
    if not batches:
        return None
    tot = sum(t1 - t0 for _, _, calls in batches for _, t0, t1 in calls)
    return tot / len(batches) / 1e6
