"""Where a traced run's host time goes: the readings the program's own
spans and thread-CPU records give beside the result line.

    python bench/hostsplit.py --workload <name> --seed <n> --seconds <s>

runs one cell traced, as ``bench/run.py --trace 1`` does, prints its
result line, then one JSON line of host readings:

- ``outside_batch_s``: device time of the step programs that falls
  outside every ``serve.generate_batch`` span.  Each batch's device work
  starts after its span opens and ends before the span's final sync, so
  this reads near 0 when the marker puts the profiler and the spans on
  one clock; a reading of a sizeable share of ``busy_s`` means the offset
  is off, and no idle gap can be named until it is mended.
- ``idle_gaps``: the longest device idle gaps, named by the server's
  host spans, worker states and serve phases included.
- ``cpu_ms_per_req``: host CPU per completed request over the window by
  thread phase (the program's ``task_clock_ns`` records), and ``other``:
  the processes' whole CPU (``host_cpu_ms_per_req``) less those.
- ``worker_cover``: share of the profiled sub-window that the dispatcher
  worker's four states (idle, batch wait, handler, complete) cover.
- ``span_ms``: mean of each worker state and serve-step phase, over the
  spans that started in the profiled sub-window.
- ``latency_p50_ms``, ``host_cpu_ms_per_req``, ``mean_batch``: the
  traced run's own end-to-end readings, to hold against an untraced run
  of the same seed (the cost of tracing).
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

import numpy as np  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for _p in (ROOT, os.path.join(ROOT, "src")):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from bench import xtrace  # noqa: E402

#: the server's host spans that can name a device idle gap: the
#: harness's own list, then the worker's states and the step's phases
#: (``dispatcher.queue`` is a request's wait, not what a thread does)
GAP_SPANS = ("serve.generate_batch", "dispatcher.handler",
             "dispatcher.gather", "dispatcher.batch_wait",
             "reactor.reply_fill", "reactor.drain",
             "dispatcher.idle", "dispatcher.complete", "serve.h2d",
             "serve.prefill", "serve.decode", "serve.sync")
WORKER_STATES = ("dispatcher.idle", "dispatcher.batch_wait",
                 "dispatcher.handler", "dispatcher.complete")
SERVE_PHASES = ("serve.generate_batch", "serve.h2d", "serve.prefill",
                "serve.decode", "serve.sync")
STEP_PROGRAMS = ("jit_prefill", "jit_decode")


def within(intervals, lo: float, hi: float) -> list:
    """The ``(t0, t1)`` that overlap ``[lo, hi]``, clipped to it
    (``xtrace.union`` expects no interval wholly outside its bounds)."""
    return [(max(a, lo), min(b, hi)) for a, b in intervals
            if a < hi and b > lo]


def outside_batch_ns(trace, spans, lo: float, hi: float) -> float:
    """Device time in ``[lo, hi]`` of the ``jit_prefill``/``jit_decode``
    module events that no ``(t0, t1)`` of ``spans`` (the
    ``serve.generate_batch`` spans) covers."""
    out = 0.0
    for evs in trace.modules.values():
        for name, t0, t1 in evs:
            if xtrace.program_name(name) not in STEP_PROGRAMS:
                continue
            t0, t1 = max(t0, lo), min(t1, hi)
            if t1 > t0:
                out += (t1 - t0) - xtrace.union(within(spans, t0, t1),
                                                t0, t1)[0]
    return out


def _kinds() -> dict:
    from repro.obs.trace import KIND_NAMES
    return {v: k for k, v in KIND_NAMES.items()}


def _spans(run, name: str, lo: float, hi: float) -> list:
    if name not in _kinds():
        return []
    return [(float(r["t0"]), float(r["t1"]))
            for r in run.span_records(name, lo, hi)]


def cpu_split(run) -> dict:
    """Host CPU ms per completed request over the window, by the phase
    the program's ``task_clock_ns`` records name, plus ``other``."""
    n = run.completed_in_window()
    kinds = _kinds()
    if not n or "ctr.task_clock_ns" not in kinds:
        return {}
    names = {k: v for v, k in kinds.items()}
    recs = run.span_records("ctr.task_clock_ns", run.t0 * 1e9,
                            run.t_end * 1e9)
    out: dict = {}
    for arg in np.unique(recs["arg"]):
        sel = recs[recs["arg"] == arg]
        ns = float((sel["t1"] - sel["t0"]).astype(np.int64).sum())
        out[names.get(int(arg), f"kind{arg}")] = ns / 1e6 / n
    out["other"] = run.cpu_s * 1e3 / n - sum(out.values())
    return out


def worker_cover(run) -> float | None:
    """Share of the profiled sub-window the dispatcher worker's states
    cover (one worker: the fabric the harness builds has one)."""
    lo, hi = run.trace_lo, run.trace_hi
    spans = [s for name in WORKER_STATES
             for s in _spans(run, name, lo - 60e9, hi)]
    if not spans or hi <= lo:
        return None
    return xtrace.union(within(spans, lo, hi), lo, hi)[0] / (hi - lo)


def span_ms(run) -> dict:
    """Mean ms of each worker state and serve phase in the sub-window."""
    out = {}
    for name in WORKER_STATES + SERVE_PHASES:
        spans = _spans(run, name, run.trace_lo, run.trace_hi)
        if spans:
            out[name] = sum(b - a for a, b in spans) / len(spans) / 1e6
    return out


def report(run, line: dict) -> dict:
    """The host readings of one traced run (see the module docstring)."""
    from bench.harness import metric_reader
    lo, hi = run.trace_lo, run.trace_hi
    out = {"spans_drops": run.spans.total_drops if run.spans else None,
           "latency_p50_ms": metric_reader("latency_p50_ms")(run),
           "host_cpu_ms_per_req": metric_reader("host_cpu_ms_per_req")(run),
           "mean_batch": line["load"]["mean_batch"],
           "cpu_ms_per_req": cpu_split(run) if run.spans else {},
           "worker_cover": worker_cover(run) if run.spans else None,
           "span_ms": span_ms(run) if run.spans else {}}
    if run.dtrace is not None and run.spans is not None:
        batches = _spans(run, "serve.generate_batch", lo - 60e9, hi + 60e9)
        busy = within([(a, b) for evs in run.dtrace.ops.values()
                       for _, a, b in evs], lo, hi)
        busy_ns, gaps = xtrace.union(busy, lo, hi)
        out["busy_s"] = busy_ns / 1e9
        out["outside_batch_s"] = outside_batch_ns(run.dtrace, batches,
                                                  lo, hi) / 1e9
        named = [(name, a, b) for name in GAP_SPANS
                 for a, b in _spans(run, name, lo - 60e9, hi)]
        out["idle_gaps"] = xtrace.name_gaps(gaps, named)
    return out


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args()

    from bench import harness
    runs = []
    check = harness.check_outputs

    def keep_run(run, *a, **kw):        # the one hook into the harness
        runs.append(run)
        return check(run, *a, **kw)
    harness.check_outputs = keep_run
    line = harness.run_cell(args.workload, args.seed, args.seconds, True,
                            platform="tpu", t_start=T_START)
    print(json.dumps(line), flush=True)
    print(json.dumps({"host": report(runs[0], line)}), flush=True)


if __name__ == "__main__":
    main()
