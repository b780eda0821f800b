"""Microseconds of host CPU per completed request that the fabric's
polling spends: the reactor thread's loop, each client's receiver loop
and each client's completion waits, from the program's thread-CPU counter
records (``ctr.task_clock_ns`` of the phases below), summed over every
process in the profiled sub-window, per request completed in it.  It is
the IPC layer's part of ``host_cpu_ms_per_req``.  Nothing is read where a
span ring wrapped and lost records, where no device trace was taken, or
where the program has no such records."""
import numpy as np

PHASES = ("reactor.loop", "client.recv_loop", "client.query_wait")
CPU = "ctr.task_clock_ns"


def read(run):
    if run.spans is None or run.dtrace is None or run.spans.total_drops:
        return None
    from repro.obs.trace import KIND_NAMES
    kinds = {v: k for k, v in KIND_NAMES.items()}
    if any(name not in kinds for name in PHASES + (CPU,)):
        return None
    lo, hi = run.trace_lo, run.trace_hi
    recs = run.span_records(CPU, lo, hi)
    recs = recs[np.isin(recs["arg"], [kinds[p] for p in PHASES])]
    ns = float((recs["t1"] - recs["t0"]).astype(np.int64).sum())
    n = sum(1 for r in run.requests if lo <= r["done"] * 1e9 <= hi)
    return ns / 1e3 / n if n and ns else None
