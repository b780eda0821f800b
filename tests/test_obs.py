"""Observability plane: trace rings, cross-process join, metrics registry.

The trace tests drive the real shared-memory span rings (enable → emit →
collect) inside one process first — wraparound loss accounting, span
nesting, Chrome export — then prove the headline property end to end: a
request issued by a *spawned client process* produces spans on both sides
of the fabric that join into one timeline on the request id, and the
client-side phase spans sum to the measured end-to-end latency.

The disabled-path test is the counted zero-overhead gate: tracing off
must write exactly 0 records (``emitted_count()``), not "few".
"""
import json
import multiprocessing as mp
import threading
import time

import numpy as np
import pytest

from repro.core.dispatcher import RequestDispatcher
from repro.core.policy import ExecutionMode, OffloadPolicy
from repro.ipc import RemoteDispatcherClient, ServingFabric, TransportSpec
from repro.obs import hist as obs_hist
from repro.obs import metrics as obs_metrics
from repro.obs import trace as obs_trace

from conftest import wait_until

TIGHT = OffloadPolicy(offload_threshold_bytes=1, poll_interval_us=50.0)
SMALL = TransportSpec(data_slots=4, data_slot_bytes=1 << 20,
                      ctrl_slots=4, ctrl_slot_bytes=4 << 10)


@pytest.fixture
def traced():
    """Fresh trace session; everything unlinked afterwards no matter what."""
    session = obs_trace.enable(capacity=1 << 12)
    try:
        yield session
    finally:
        obs_trace.collect(session, unlink=True)
        obs_trace.disable(unlink=True)


# ---------------------------------------------------------------------------
# disabled = zero records (the counted gate)
# ---------------------------------------------------------------------------

def test_disabled_tracing_writes_exactly_zero_records():
    assert not obs_trace.TRACE.enabled
    before = obs_trace.emitted_count()
    t0 = obs_trace.now()
    obs_trace.emit(obs_trace.HANDLER, t0, rid=1, arg=2)
    obs_trace.instant(obs_trace.GOV_OBSERVE)
    with obs_trace.span(obs_trace.GATHER):
        pass
    assert obs_trace.emitted_count() == before == 0
    assert obs_trace.dropped_count() == 0


def test_disabled_fabric_roundtrip_writes_zero_records_and_clean_wire():
    """An instrumented end-to-end request with tracing off: no records,
    and no rid key smuggled into reply headers."""
    assert not obs_trace.TRACE.enabled
    d = RequestDispatcher(TIGHT)
    d.register_handler("double", lambda x: x * 2,
                       batch_fn=lambda xs: [x * 2 for x in xs])
    with ServingFabric(d, spec=SMALL, policy=TIGHT,
                       own_dispatcher=True).start() as fab:
        client = RemoteDispatcherClient.connect(fab.name, policy=TIGHT)
        out = client.request("double", np.arange(8, dtype=np.float32),
                             mode="sync")
        np.testing.assert_array_equal(out, np.arange(8, dtype=np.float32) * 2)
        # the pipelined path too: batch formation, queue and worker-state
        # sites, the completion wait, and the polling loops' CPU meters
        jid = client.request("double", np.ones(8, np.float32),
                             mode="pipelined")
        np.testing.assert_array_equal(client.query(jid, timeout=30),
                                      np.full(8, 2, np.float32))
        time.sleep(0.12)            # past one loop-meter period
        client.close()
    assert obs_trace.emitted_count() == 0


# ---------------------------------------------------------------------------
# single-process ring mechanics
# ---------------------------------------------------------------------------

def test_span_nesting_and_collection(traced):
    rid = obs_trace.mint_rid()
    with obs_trace.span(obs_trace.HANDLER, rid=rid, arg=3):
        time.sleep(0.002)
        with obs_trace.span(obs_trace.GATHER, rid=rid):
            time.sleep(0.001)
    view = obs_trace.collect(traced)
    assert view.total_records == 2 and view.total_drops == 0
    outer = view.records_of(obs_trace.HANDLER)[0]
    inner = view.records_of(obs_trace.GATHER)[0]
    # nested span sits strictly inside its parent on the shared timebase
    assert outer["t0"] <= inner["t0"] <= inner["t1"] <= outer["t1"]
    assert int(outer["rid"]) == int(inner["rid"]) == rid
    assert int(outer["arg"]) == 3
    totals = view.phase_totals()
    assert totals["dispatcher.handler"][0] == 1
    assert totals["dispatcher.handler"][1] >= totals["dispatcher.gather"][1]
    assert view.kinds_for_rid(rid).keys() == {obs_trace.HANDLER,
                                              obs_trace.GATHER}


def test_wraparound_overwrites_oldest_and_counts_drops():
    cap = 64
    session = obs_trace.enable(capacity=cap)
    try:
        n = 3 * cap + 7
        for i in range(n):
            t = obs_trace.now()
            obs_trace.emit(obs_trace.COPY_JOB, t, arg=i, t1=t)
        assert obs_trace.emitted_count() == n
        assert obs_trace.dropped_count() == n - cap
        view = obs_trace.collect(session)
        assert view.total_records == cap          # ring holds the newest cap
        assert view.total_drops == n - cap        # loss is counted, not silent
        args = view.records_of(obs_trace.COPY_JOB)["arg"]
        # survivors are exactly the newest records, oldest → newest order
        assert list(args) == list(range(n - cap, n))
    finally:
        obs_trace.collect(session, unlink=True)
        obs_trace.disable(unlink=True)


def test_collect_unlink_destroys_rings(traced):
    obs_trace.instant(obs_trace.GOV_OBSERVE)
    assert obs_trace.discover(traced)
    view = obs_trace.collect(traced, unlink=True)
    assert view.total_records == 1
    assert obs_trace.discover(traced) == []


def test_chrome_trace_export_is_valid_json(traced, tmp_path):
    rid = obs_trace.mint_rid()
    with obs_trace.span(obs_trace.CLIENT_SEND, rid=rid, arg=4096):
        time.sleep(0.001)
    view = obs_trace.collect(traced)
    path = tmp_path / "trace.json"
    view.save_chrome(str(path))
    doc = json.loads(path.read_text())          # must round-trip as JSON
    events = doc["traceEvents"]
    assert len(events) == 1
    ev = events[0]
    assert ev["ph"] == "X" and ev["name"] == "client.send"
    assert ev["dur"] >= 1000.0                  # µs; slept 1 ms inside
    assert ev["args"]["rid"] == rid and ev["args"]["arg"] == 4096
    assert doc["otherData"]["drops"] == 0


# ---------------------------------------------------------------------------
# cross-process: spawned client's spans join the server's on the rid
# ---------------------------------------------------------------------------

def _traced_client_entry(name: str, out_q) -> None:
    """Spawn-child: tracing auto-enabled by the inherited environment; one
    pipelined request, report (rid, measured e2e ns)."""
    from repro.obs import trace as child_trace
    assert child_trace.TRACE.enabled           # env inheritance worked
    client = RemoteDispatcherClient.connect(name, policy=TIGHT, timeout_s=60)
    data = np.arange(1 << 14, dtype=np.float32)
    t0 = child_trace.now()
    jid = client.request("slow", data, mode="pipelined")
    rid = client.queries._meta[jid].rid        # the wait drops it; grab it now
    out = client.query(jid, timeout=60)
    e2e_ns = child_trace.now() - t0
    client.close()
    ok = bool(np.array_equal(out, data * 2))
    out_q.put((rid, e2e_ns, ok))


def test_cross_process_rid_join_and_phase_sum(tmp_path):
    def slow(x):
        time.sleep(0.02)
        return x * 2

    d = RequestDispatcher(TIGHT)
    d.register_handler("slow", slow, batch_fn=lambda xs: [slow(x) for x in xs])
    session = obs_trace.enable(capacity=1 << 14)
    try:
        with ServingFabric(d, spec=SMALL, policy=TIGHT,
                           own_dispatcher=True).start() as fab:
            ctx = mp.get_context("spawn")
            out_q = ctx.Queue()
            proc = ctx.Process(target=_traced_client_entry,
                               args=(fab.name, out_q))
            proc.start()
            rid, e2e_ns, ok = out_q.get(timeout=120)
            proc.join(timeout=120)
            assert proc.exitcode == 0 and ok
        view = obs_trace.collect(session)
        assert view.total_drops == 0
        # spans from BOTH processes landed in one session
        child_pid = proc.pid
        assert child_pid in view.pids and len(view.pids) >= 2
        joined = view.kinds_for_rid(rid)
        # client side of the request…
        assert obs_trace.CLIENT_SEND in joined
        assert obs_trace.QUERY_WAIT in joined
        # …joins the server side on the same rid (byte-exact through the wire)
        assert obs_trace.HANDLER in joined
        assert obs_trace.REPLY_FILL in joined
        client_kinds = {k for k, spans in joined.items()
                        if any(pid == child_pid for pid, _, _ in spans)}
        server_kinds = {k for k, spans in joined.items()
                        if any(pid != child_pid for pid, _, _ in spans)}
        assert obs_trace.CLIENT_SEND in client_kinds
        assert obs_trace.HANDLER in server_kinds

        # the client's phase spans decompose its measured e2e latency: send
        # + completion-wait cover everything but sub-µs bookkeeping, so the
        # sum lands within 10% of the wall clock the child itself measured
        client_ns = sum(t1 - t0 for kind in (obs_trace.CLIENT_SEND,
                                             obs_trace.QUERY_WAIT)
                        for pid, t0, t1 in joined[kind] if pid == child_pid)
        assert abs(client_ns - e2e_ns) <= 0.10 * e2e_ns, (client_ns, e2e_ns)

        # and the joined timeline exports as loadable Chrome-trace JSON
        path = tmp_path / "xproc.json"
        view.save_chrome(str(path))
        doc = json.loads(path.read_text())
        assert {e["pid"] for e in doc["traceEvents"]} >= {child_pid}
    finally:
        obs_trace.collect(session, unlink=True)
        obs_trace.disable(unlink=True)


# ---------------------------------------------------------------------------
# host accounting: queue spans, worker states, serve phases, loop CPU
# ---------------------------------------------------------------------------

def _union_ns(intervals) -> int:
    covered, cur = 0, None
    for t0, t1 in sorted(intervals):
        if cur is None or t0 > cur:
            covered += t1 - t0
            cur = t1
        elif t1 > cur:
            covered += t1 - cur
            cur = t1
    return covered


def test_queue_spans_give_each_batch_its_exact_composition(traced):
    seen = []                      # rows of every handler call, in order

    def solo(x):
        seen.append([int(x[0])])
        return x

    def batch(xs):
        seen.append([int(x[0]) for x in xs])
        return list(xs)

    d = RequestDispatcher(OffloadPolicy(max_batch=4), max_batch_wait_s=0.05)
    d.register_handler("echo", solo, batch_fn=batch)
    done = []
    items = [{"op": "echo", "data": np.array([i], np.int64),
              "mode": "pipelined", "rid": 100 + i,
              "on_complete": lambda j, out: done.append(j)}
             for i in range(10)]
    d.submit_many(items[:7])
    wait_until(lambda: len(done) >= 7, 10, desc="first 7 replies")
    d.submit_many(items[7:])
    wait_until(lambda: len(done) == 10, 10, desc="all replies")
    d.close()
    recs = obs_trace.collect(traced).records_of(obs_trace.DISPATCH_QUEUE)
    # one span per request, each from its arrival to its pop
    assert sorted(int(r) for r in recs["rid"]) == list(range(100, 110))
    assert (recs["t1"] >= recs["t0"]).all()
    by_seq: dict = {}
    for r in recs:
        by_seq.setdefault(int(r["arg"]), []).append(int(r["rid"]) - 100)
    # the batch number in arg reads each handler call's composition back
    assert [sorted(v) for _, v in sorted(by_seq.items())] == \
        [sorted(s) for s in seen]
    assert [len(s) for s in seen] == [4, 3, 3]


def test_worker_states_cover_the_dispatcher_thread(traced):
    d = RequestDispatcher(TIGHT)
    d.register_handler("double", lambda x: x * 2,
                       batch_fn=lambda xs: [x * 2 for x in xs])
    with ServingFabric(d, spec=SMALL, policy=TIGHT,
                       own_dispatcher=True).start() as fab:
        client = RemoteDispatcherClient.connect(fab.name, policy=TIGHT)
        for burst in range(3):
            jids = [client.request("double", np.full(64, i, np.float32),
                                   mode="pipelined") for i in range(5)]
            for j in jids:
                client.query(j, timeout=30)
            time.sleep(0.35)       # an empty stretch of three get() timeouts
        client.close()
    view = obs_trace.collect(traced)
    states = (obs_trace.DISPATCH_IDLE, obs_trace.DISPATCH_WAIT,
              obs_trace.HANDLER, obs_trace.DISPATCH_COMPLETE)
    ring = next(r for r in view.rings
                if (r.records["kind"] == obs_trace.DISPATCH_IDLE).any())
    recs = ring.records[np.isin(ring.records["kind"], states)]
    spans = [(int(a), int(b)) for a, b in zip(recs["t0"], recs["t1"])]
    wall = max(b for _, b in spans) - min(a for a, _ in spans)
    assert _union_ns(spans) >= 0.95 * wall, (_union_ns(spans), wall)
    assert {int(k) for k in recs["kind"]} == set(states)
    # an empty stretch is one idle span, not one per 0.1 s get() timeout
    idle = recs[recs["kind"] == obs_trace.DISPATCH_IDLE]
    assert (idle["t1"] - idle["t0"]).max() >= 0.3e9


def test_serve_phase_spans_nest_in_generate_batch(rng_key):
    from repro.configs import get_smoke_config
    from repro.models import build_model
    from repro.serve import BatchedServer, ServeConfig

    cfg = get_smoke_config("qwen3-32b")
    model = build_model(cfg)
    srv = BatchedServer(model, model.init(rng_key),
                        ServeConfig(max_len=32, max_new_tokens=3),
                        OffloadPolicy(max_batch=4))
    toks = [np.arange(1, 6, dtype=np.int32)] * 2
    untraced = srv.generate_batch(srv._pack(toks))
    assert obs_trace.emitted_count() == 0
    session = obs_trace.enable(capacity=1 << 10)
    try:
        for _ in range(2):
            np.testing.assert_array_equal(
                srv.generate_batch(srv._pack(toks)), untraced)
        view = obs_trace.collect(session)
    finally:
        obs_trace.collect(session, unlink=True)
        obs_trace.disable(unlink=True)
        srv.close()
    outer = view.records_of(obs_trace.SERVE_BATCH)
    assert len(outer) == 2
    phases = (obs_trace.SERVE_H2D, obs_trace.SERVE_PREFILL,
              obs_trace.SERVE_DECODE, obs_trace.SERVE_SYNC)
    for o in outer:
        inner = [view.records_of(k) for k in phases]
        inner = [r[(r["t0"] >= o["t0"]) & (r["t1"] <= o["t1"])]
                 for r in inner]
        # one of each phase per batch (never one per decode step), back to
        # back from the batch's start to its end, each with the batch rows
        assert [len(r) for r in inner] == [1, 1, 1, 1]
        bounds = [(int(r[0]["t0"]), int(r[0]["t1"])) for r in inner]
        assert bounds[0][0] == int(o["t0"]) and bounds[-1][1] == int(o["t1"])
        assert all(a[1] == b[0] for a, b in zip(bounds, bounds[1:]))
        assert all(int(r[0]["arg"]) == 2 for r in inner)


def test_loop_cpu_records_ride_on_tracing_alone(traced):
    from repro.obs import hwcounters as hw
    assert not hw.PROF.enabled

    def slow(x):
        time.sleep(0.15)           # longer than query()'s wait slices
        return x * 2

    d = RequestDispatcher(TIGHT)
    d.register_handler("slow", slow, batch_fn=lambda xs: [slow(x)
                                                          for x in xs])
    with ServingFabric(d, spec=SMALL, policy=TIGHT,
                       own_dispatcher=True).start() as fab:
        client = RemoteDispatcherClient.connect(fab.name, policy=TIGHT)
        for i in range(3):
            jid = client.request("slow", np.full(16, i, np.float32),
                                 mode="pipelined")
            client.query(jid, timeout=30)
        rids = [r.rid for r in client.queries._meta.values()]
        client.close()
    assert rids == []              # every completed wait forgot its job
    view = obs_trace.collect(traced)
    assert hw.scope_count() == 0   # no phase profiling was switched on
    folded = hw.counters_from_view(view)
    for phase in ("reactor_loop", "recv_loop", "dispatcher_loop",
                  "query_wait"):
        assert "task_clock_ns" in folded.get(phase, {}), (phase, folded)
    assert folded["reactor_loop"]["task_clock_ns"] > 0
    # one loop record per >= LOOP_PERIOD_NS of loop, the last one excepted
    cpu = view.records_of(obs_trace.CTR_KINDS["task_clock_ns"])
    loop = np.sort(cpu[cpu["arg"] == obs_trace.REACTOR_LOOP]["t0"])
    assert len(loop) >= 2
    assert (np.diff(loop.astype(np.int64)) >= hw.LOOP_PERIOD_NS).all()
    # client.query_wait: exactly one span per wait, from the first of its
    # slices to the reply, carrying the request's rid
    waits = view.records_of(obs_trace.QUERY_WAIT)
    sends = view.records_of(obs_trace.CLIENT_SEND)
    assert len(waits) == 3
    assert sorted(waits["rid"]) == sorted(sends["rid"])
    assert ((waits["t1"] - waits["t0"]) >= 0.1e9).all()
    assert sorted(cpu[cpu["arg"] == obs_trace.QUERY_WAIT]["rid"]) == \
        sorted(waits["rid"])


def test_query_wait_emits_one_span_and_cpu_record(traced):
    """A wait made in two query() calls (the first times out) is one
    client.query_wait span and one thread-CPU record, with the rid."""
    from repro.core.dispatcher import QueryHandler, Request
    qh = QueryHandler()
    qh.register(Request(7, "op", None, ExecutionMode.PIPELINED, rid=99))
    with pytest.raises(TimeoutError):
        qh.query(7, timeout=0.05)
    timer = threading.Timer(0.05, qh.complete, args=(7, "out"))
    timer.start()
    assert qh.query(7, timeout=5) == "out"
    timer.join()
    view = obs_trace.collect(traced)
    waits = view.records_of(obs_trace.QUERY_WAIT)
    assert list(waits["rid"]) == [99]
    assert int(waits[0]["t1"] - waits[0]["t0"]) >= 0.09e9
    cpu = view.records_of(obs_trace.CTR_KINDS["task_clock_ns"])
    assert list(cpu[cpu["arg"] == obs_trace.QUERY_WAIT]["rid"]) == [99]


# ---------------------------------------------------------------------------
# metrics registry + SLO tracker
# ---------------------------------------------------------------------------

class _SnapStats:
    def snapshot(self):
        return {"a": 1, "nested": {"b": 2.5}}


def test_metrics_registry_snapshot_shapes_and_delta():
    reg = obs_metrics.MetricsRegistry()
    reg.register("dict", {"x": 1})
    reg.register("call", lambda: {"y": 2})
    reg.register("snap", _SnapStats())
    assert reg.names() == ["call", "dict", "snap"]
    snap = reg.snapshot()
    assert snap == {"dict.x": 1, "call.y": 2,
                    "snap.a": 1, "snap.nested.b": 2.5}
    later = dict(snap, **{"call.y": 10, "snap.nested.b": 3.0, "tag": "v"})
    delta = obs_metrics.MetricsRegistry.delta(snap, later)
    assert delta["call.y"] == 8
    assert delta["snap.nested.b"] == 0.5
    assert delta["dict.x"] == 0
    assert delta["tag"] == "v"                 # non-numeric passes through
    reg.unregister("dict")
    assert "dict.x" not in reg.snapshot()


def test_slo_tracker_observes_and_rates_model():
    from repro.core.latency import LatencyModel
    model = LatencyModel(l_fixed_us=10.0, alpha_us_per_mb=100.0)
    slo = obs_metrics.SLOTracker(model, window=16)
    for _ in range(8):
        slo.observe(0.001, nbytes=1 << 20)     # 1 ms on 1 MB
    snap = slo.snapshot()
    assert snap["requests"] == 8
    assert snap["mb_in"] == pytest.approx(8.0)
    assert snap["p50_ms"] == pytest.approx(1.0, rel=0.2)
    # predicted 110 µs vs observed 1 ms → ratio ≈ 9.09, EWMA of a constant
    assert snap["model_ratio"] == pytest.approx(1000.0 / 110.0, rel=0.05)


def test_fabric_exposes_unified_metrics_and_slo():
    d = RequestDispatcher(TIGHT)
    d.register_handler("double", lambda x: x * 2,
                       batch_fn=lambda xs: [x * 2 for x in xs])
    with ServingFabric(d, spec=SMALL, policy=TIGHT,
                       own_dispatcher=True).start() as fab:
        client = RemoteDispatcherClient.connect(fab.name, policy=TIGHT)
        for _ in range(3):
            client.request("double", np.ones(16, np.float32), mode="sync")
        # reply send and observe() race: wait for the bookkeeping to land
        wait_until(lambda: fab.slo.requests >= 3, 10,
                   desc="3 slo observations")
        snap = fab.metrics.snapshot()
        full = fab.stats()
        client.close()
    assert snap["slo.requests"] >= 3
    assert snap["slo.p50_ms"] > 0
    assert snap["listener.accepted"] == 1
    assert any(k.startswith("reactor.") for k in snap)
    assert any(k.startswith("dispatcher.") for k in snap)
    assert full["slo"]["requests"] >= 3
    assert full["metrics"]["slo.requests"] >= 3


# ---------------------------------------------------------------------------
# histograms
# ---------------------------------------------------------------------------

def test_histogram_buckets_merge_and_percentile():
    h = obs_hist.Histogram()
    h.add(0)
    h.add(1)
    h.add(1000)
    assert h.counts[0] == 1                    # zeros live in bucket 0
    assert h.counts[1] == 1                    # 2^0 <= 1 < 2^1
    assert h.counts[10] == 1                   # 2^9 <= 1000 < 2^10
    assert h.n == 3 and h.total == 1001
    assert h.mean == pytest.approx(1001 / 3)

    g = obs_hist.Histogram.from_durations(np.full(97, 1000, np.int64))
    g.merge(h)
    assert g.n == 100 and g.total == 97 * 1000 + 1001
    # 100 values, 97 of them 1000 → p95 falls in the 1000s bucket
    assert 512 <= g.percentile(95) <= 1023
    assert g.percentile(1) == 0

    rt = obs_hist.Histogram.from_dict(g.to_dict())
    assert rt.n == g.n and rt.total == g.total
    assert np.array_equal(rt.counts, g.counts)


def test_phase_histograms_and_report_from_view(traced):
    for _ in range(4):
        with obs_trace.span(obs_trace.RING_WAIT):
            time.sleep(0.001)
    view = obs_trace.collect(traced)
    hists = obs_hist.phase_histograms(view)
    assert set(hists) == {"ring.wait"}
    assert hists["ring.wait"].n == 4
    assert hists["ring.wait"].mean >= 1e6      # slept ≥ 1 ms per span
    report = obs_hist.phase_report(view, per=4)
    assert "ring.wait" in report and "us/item" in report
