"""Median time a request waited in the dispatcher's queue, from its
arrival in ``submit`` to its pop into a batch (the program's
``dispatcher.queue`` spans), over the requests that arrived in the
profiled sub-window: the stretch ``batch_dev_ms.open`` reads, so that the
two describe the same requests.  Nothing is read where no device trace
was taken or the program has no such span."""
import numpy as np

SPAN = "dispatcher.queue"


def read(run):
    if run.spans is None or run.dtrace is None:
        return None
    from repro.obs.trace import KIND_NAMES
    if SPAN not in KIND_NAMES.values():
        return None
    recs = run.span_records(SPAN, run.trace_lo, run.trace_hi)
    if not len(recs):
        return None
    return float(np.median((recs["t1"] - recs["t0"]).astype(np.int64))) / 1e6
