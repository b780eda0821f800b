"""The readers of the program's host spans and thread-CPU records, and
the host readings of ``bench/hostsplit.py``, on synthetic runs: records
inside and outside the profiled sub-window, a ring that lost records, and
a program that has no such span."""
import json
import os

import numpy as np
import pytest

import _bench_path
from bench import hostsplit, xtrace
from bench.harness import Run, metric_reader
from bench.models import transformer as tf
from repro.obs import trace as rtrace

MS = 1e6                                     # ns
KIND = {v: k for k, v in rtrace.KIND_NAMES.items()}
CPU = rtrace.CTR_KINDS["task_clock_ns"]


def _run(records, drops=0):
    """A traced run whose profiled sub-window is [100, 1100] ms of a
    [0, 2000] ms window; ``records``: ``(kind name, arg, t0 ms, t1 ms)``
    (a CPU record's t1 - t0 is its CPU)."""
    with open(os.path.join(_bench_path.ROOT, "bench", "configs",
                           "granite-moe-1b-a400m.json")) as f:
        arch = tf.Arch.from_dict(json.load(f)["arch"])
    run = Run("moe-classify-open", 1, 2.0, 1, arch, tf,
              {"prompt_len": 256, "new_tokens": 1})
    run.t0, run.t_end = 0.0, 2.0
    run.trace_lo, run.trace_hi = 100 * MS, 1100 * MS
    run.dtrace = xtrace.DeviceTrace(ops={"d": []}, modules={"d": []})
    recs = np.array([(KIND.get(k, CPU if k == "cpu" else 0), a,
                      int(t0 * MS), int(t1 * MS), 0)
                     for k, a, t0, t1 in records], rtrace.RECORD_DTYPE)
    run.spans = rtrace.TraceView([rtrace.RingDump("r", 1, 1, drops, recs)])
    # 4 requests done inside the sub-window, 2 after it
    run.requests = [{"due": d, "sent": d, "done": d + 0.05}
                    for d in (0.2, 0.4, 0.6, 0.8, 1.2, 1.4)]
    run.cpu_s = 0.6
    return run


def test_queue_wait_is_the_median_over_arrivals_in_the_sub_window():
    run = _run([("dispatcher.queue", 1, 50, 70),      # arrived before it
                ("dispatcher.queue", 1, 200, 210),
                ("dispatcher.queue", 2, 300, 330),
                ("dispatcher.queue", 2, 400, 500),
                ("dispatcher.queue", 3, 1200, 1900)])  # arrived after it
    assert metric_reader("queue_wait_ms.open")(run) == pytest.approx(30.0)


def test_h2d_is_the_mean_copy_of_the_batches_in_the_sub_window():
    run = _run([("serve.h2d", 4, 150, 151), ("serve.h2d", 2, 600, 604),
                ("serve.h2d", 3, 1500, 1600),
                ("serve.prefill", 4, 151, 160)])
    assert metric_reader("h2d_ms.open")(run) == pytest.approx(2.5)


def test_ipc_cpu_sums_the_polling_phases_per_completed_request():
    loops = [("cpu", KIND["reactor.loop"], 100, 140),
             ("cpu", KIND["client.recv_loop"], 500, 520),
             ("cpu", KIND["client.query_wait"], 700, 702),
             # not the fabric's polling, or outside the sub-window
             ("cpu", KIND["dispatcher.loop"], 300, 400),
             ("cpu", KIND["reactor.loop"], 1200, 1300)]
    read = metric_reader("ipc_cpu_us_per_req")
    # 62 ms of CPU over the 4 requests completed in the sub-window
    assert read(_run(loops)) == pytest.approx(62e3 / 4)
    assert read(_run(loops, drops=1)) is None


@pytest.mark.parametrize("name", ["queue_wait_ms.open", "h2d_ms.open",
                                  "ipc_cpu_us_per_req"])
def test_readers_say_nothing_where_there_is_nothing_to_read(name,
                                                            monkeypatch):
    records = [("dispatcher.queue", 1, 200, 210), ("serve.h2d", 1, 200, 201),
               ("cpu", KIND["reactor.loop"], 200, 250)]
    read = metric_reader(name)
    assert read(_run(records)) is not None
    no_device = _run(records)
    no_device.dtrace = None
    assert read(no_device) is None
    # a program without the new spans (the parent of this change) reads
    # nothing and raises nothing
    kept = {k: v for k, v in rtrace.KIND_NAMES.items()
            if v not in ("dispatcher.queue", "serve.h2d", "reactor.loop",
                         "client.recv_loop")}
    monkeypatch.setattr(rtrace, "KIND_NAMES", kept)
    assert read(_run([])) is None


def test_outside_batch_counts_step_programs_no_batch_span_covers():
    mods = [("jit_prefill(1)", 10 * MS, 20 * MS),      # inside batch 1
            ("jit_decode(2)", 25 * MS, 35 * MS),       # 5 ms past its end
            ("jit_decode(2)", 50 * MS, 52 * MS),       # in no batch
            ("jit_prefill(1)", 95 * MS, 130 * MS),     # 10 ms past hi
            ("jit_other(3)", 60 * MS, 70 * MS)]        # not a step program
    trace = xtrace.DeviceTrace(ops={"d": mods}, modules={"d": mods})
    batches = [(5 * MS, 30 * MS), (90 * MS, 140 * MS)]
    got = hostsplit.outside_batch_ns(trace, batches, 0.0, 120 * MS)
    assert got == pytest.approx(5 * MS + 2 * MS)


def test_cpu_split_names_each_phase_and_leaves_the_rest_to_other():
    run = _run([("cpu", KIND["reactor.loop"], 100, 160),
                ("cpu", KIND["client.query_wait"], 500, 530),
                ("cpu", KIND["reactor.loop"], 1500, 1510),
                ("cpu", KIND["dispatcher.loop"], 2100, 2300)])  # after
    split = hostsplit.cpu_split(run)
    # 6 requests completed in the window, 600 ms of CPU in all
    assert split == pytest.approx({"reactor.loop": 70 / 6,
                                   "client.query_wait": 30 / 6,
                                   "other": 500 / 6})


def test_worker_cover_and_named_gaps_read_the_worker_states():
    run = _run([("dispatcher.idle", 0, 50, 400),
                ("dispatcher.batch_wait", 1, 400, 402),
                ("dispatcher.handler", 1, 402, 700),
                ("serve.generate_batch", 1, 403, 699),
                ("dispatcher.complete", 1, 700, 701),
                ("dispatcher.idle", 0, 701, 1050)])
    assert hostsplit.worker_cover(run) == pytest.approx(0.95)
    # the first idle span started before the sub-window: not averaged
    assert hostsplit.span_ms(run) == pytest.approx({
        "dispatcher.idle": 349.0, "dispatcher.batch_wait": 2.0,
        "dispatcher.handler": 298.0, "dispatcher.complete": 1.0,
        "serve.generate_batch": 296.0})
    ops = [("%fusion.1", 450 * MS, 600 * MS),
           ("%fusion.2", 620 * MS, 690 * MS)]
    run.dtrace = xtrace.DeviceTrace(ops={"d": ops}, modules={"d": ops})
    line = {"load": {"mean_batch": 1.0}}
    host = hostsplit.report(run, line)
    assert host["outside_batch_s"] == 0.0
    assert [g[0] for g in host["idle_gaps"]] == [
        "dispatcher.idle", "dispatcher.idle", "serve.generate_batch"]


def test_busy_time_ignores_device_events_past_the_sub_window():
    ops = [("%fusion.1", 200 * MS, 300 * MS),
           ("%fusion.2", 1150 * MS, 1160 * MS)]      # after trace_hi
    run = _run([])
    run.dtrace = xtrace.DeviceTrace(ops={"d": ops}, modules={"d": []})
    host = hostsplit.report(run, {"load": {"mean_batch": None}})
    assert host["busy_s"] == pytest.approx(0.1)
    assert sorted(g[1] for g in host["idle_gaps"]) == pytest.approx([0.1,
                                                                     0.8])
