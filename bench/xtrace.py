"""Reduce a JAX profiler trace (XSpace) to device busy time, idle gaps
and per-program time, on the clock of the program's own spans.

The profiler stamps events in nanoseconds from the start of the trace;
the program's spans (``repro.obs.trace``) use ``time.perf_counter_ns``.
The harness wraps a marker annotation between two ``perf_counter_ns``
readings right after the trace starts, and :func:`clock_offset_ns` turns
that pair into the offset between the two clocks.
"""
from __future__ import annotations

import re
from dataclasses import dataclass, field

import numpy as np

MARKER = "bench.clock_sync"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
_DEVICE_PLANE = re.compile(r"^/device:(TPU|GPU):\d+$")


@dataclass
class DeviceTrace:
    """Events of one trace, in the program's clock (perf_counter ns)."""
    ops: dict = field(default_factory=dict)      # plane -> [(name, t0, t1)]
    modules: dict = field(default_factory=dict)  # plane -> [(name, t0, t1)]


def load(path: str):
    from jax.profiler import ProfileData
    return ProfileData.from_file(path)


def clock_offset_ns(pd, mono0: int, mono1: int) -> float:
    """Offset to add to a profiler timestamp to get ``perf_counter_ns``,
    from the :data:`MARKER` annotation made between ``mono0`` and
    ``mono1``."""
    for plane in pd.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name == MARKER:
                    return (mono0 + mono1) / 2 - (ev.start_ns + ev.end_ns) / 2
    raise LookupError(f"no {MARKER} annotation in the trace")


def device_trace(pd, offset_ns: float = 0.0) -> DeviceTrace:
    out = DeviceTrace()
    for plane in pd.planes:
        if not _DEVICE_PLANE.match(plane.name):
            continue
        for line in plane.lines:
            if line.name == OPS_LINE:
                dst = out.ops
            elif line.name == MODULES_LINE:
                dst = out.modules
            else:
                continue
            dst.setdefault(plane.name, []).extend(
                (ev.name, ev.start_ns + offset_ns, ev.end_ns + offset_ns)
                for ev in line.events)
    for d in (out.ops, out.modules):
        for plane in d:
            d[plane].sort(key=lambda e: e[1])
    return out


def union(intervals, lo: float, hi: float) -> tuple:
    """(covered ns, gaps) of ``[(t0, t1), ...]`` clipped to ``[lo, hi]``;
    gaps are the uncovered ``(t0, t1)`` stretches, in time order."""
    covered, gaps, cur = 0.0, [], lo
    for t0, t1 in sorted(intervals):
        t0, t1 = max(t0, lo), min(t1, hi)
        if t1 <= cur:
            continue
        if t0 > cur:
            gaps.append((cur, t0))
            cur = t0
        covered += t1 - cur
        cur = t1
    if cur < hi:
        gaps.append((cur, hi))
    return covered, gaps


def busy_ns(trace: DeviceTrace, lo: float, hi: float) -> float:
    """Busy time averaged over the device planes that ran anything."""
    per = [union([(a, b) for _, a, b in evs], lo, hi)[0]
           for evs in trace.ops.values()]
    per = [b for b in per if b > 0]
    return float(np.mean(per)) if per else 0.0


def top_ops(trace: DeviceTrace, lo: float, hi: float, n: int = 10) -> list:
    """The ``n`` device operations that took most time, as
    ``[["<program>/<op>", seconds], ...]``.  Only leaf operations count
    (a loop op's interval holds its body's ops); each op is named with
    the program execution it fell in and its result shape."""
    tot: dict = {}
    for plane, evs in trace.ops.items():
        mods = trace.modules.get(plane, [])
        starts = np.array([m[1] for m in mods])
        for i, (name, t0, t1) in enumerate(evs):
            if i + 1 < len(evs) and evs[i + 1][1] < t1:
                continue                      # holds the next op: not a leaf
            t0c, t1c = max(t0, lo), min(t1, hi)
            if t1c <= t0c:
                continue
            j = int(np.searchsorted(starts, t0, side="right")) - 1
            prog = program_name(mods[j][0]) if j >= 0 and \
                mods[j][2] >= t0 else "-"
            key = f"{prog}/{op_name(name)}"
            tot[key] = tot.get(key, 0.0) + (t1c - t0c)
    ranked = sorted(tot.items(), key=lambda kv: -kv[1])[:n]
    return [[k, v / 1e9] for k, v in ranked]


def op_name(hlo_text: str) -> str:
    """``%fusion.3 = bf16[8,512]{1,0:T(8,128)} fusion(...)`` ->
    ``%fusion.3 = bf16[8,512]``."""
    return hlo_text.split("{", 1)[0].split("(", 1)[0].strip()[:80]


def program_name(module_event_name: str) -> str:
    """``jit_prefill(123)`` -> ``jit_prefill``."""
    return module_event_name.split("(", 1)[0]


def name_gaps(gaps, spans, n: int = 10) -> list:
    """The ``n`` longest gaps as ``[[what the host was doing, seconds]]``.

    ``spans``: ``[(name, t0, t1), ...]`` of the server's host spans; a gap
    takes the name of the innermost span covering its midpoint, or
    ``"no span"`` where none does."""
    longest = sorted(gaps, key=lambda g: g[0] - g[1])[:n]
    out = []
    for g0, g1 in longest:
        mid = (g0 + g1) / 2
        best = None
        for name, s0, s1 in spans:
            if s0 <= mid <= s1 and (best is None or s1 - s0 < best[1]):
                best = (name, s1 - s0)
        out.append([best[0] if best else "no span", (g1 - g0) / 1e9])
    return out
