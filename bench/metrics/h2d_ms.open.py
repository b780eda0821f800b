"""Mean time of one batch's host-to-device copy in the served step: the
transfer engine's submit and wait up to the device copy's
``block_until_ready`` (the program's ``serve.h2d`` spans), over the
batches that started in the profiled sub-window.  Nothing is read where
no device trace was taken or the program has no such span."""
import numpy as np

SPAN = "serve.h2d"


def read(run):
    if run.spans is None or run.dtrace is None:
        return None
    from repro.obs.trace import KIND_NAMES
    if SPAN not in KIND_NAMES.values():
        return None
    recs = run.span_records(SPAN, run.trace_lo, run.trace_hi)
    if not len(recs):
        return None
    return float((recs["t1"] - recs["t0"]).astype(np.int64).mean()) / 1e6
