"""ROCKET core runtime: the paper's configuration semantics (Table III/§V),
latency model, engine modes, dispatcher, buffer pools."""
import time

import numpy as np
import pytest
from hypothesis import given, strategies as st

import jax

from repro.core import (
    AsyncTransferEngine,
    BufferPool,
    ExecutionMode,
    LatencyModel,
    OffloadPolicy,
    QueuePair,
    RequestDispatcher,
    calibrate,
)
from repro.core.policy import Device


# ---------------------------------------------------------------------------
# policy semantics (paper Table III + §V defaults)
# ---------------------------------------------------------------------------

def test_injection_defaults_follow_paper():
    sync = OffloadPolicy(mode=ExecutionMode.SYNC)
    async_ = OffloadPolicy(mode=ExecutionMode.ASYNC)
    pipe = OffloadPolicy(mode=ExecutionMode.PIPELINED)
    assert sync.injection_enabled(1) is True          # sync: on
    assert async_.injection_enabled(1) is True        # async single-client: on
    assert async_.injection_enabled(4) is False       # async contended: off
    assert pipe.injection_enabled(1) is False         # pipelined: off
    # explicit override wins
    assert OffloadPolicy(mode=ExecutionMode.PIPELINED,
                         cache_injection=True).injection_enabled(8) is True


def test_size_threshold_offload_control():
    pol = OffloadPolicy(offload_threshold_bytes=1024)
    assert not pol.should_offload(512)
    assert pol.should_offload(2048)
    assert not pol.with_device("inline").should_offload(1 << 30)


@given(st.integers(0, 1 << 28))
def test_latency_model_monotonic(nbytes):
    m = LatencyModel(73.6, 33.4)
    assert m.predict_us(nbytes) >= m.l_fixed_us
    assert m.defer_seconds(nbytes) <= m.predict_us(nbytes) * 1e-6


def test_latency_model_matches_paper_constants():
    m = LatencyModel()                                # paper's measured priors
    assert abs(m.predict_us(1 << 20) - (73.6 + 33.4)) < 1e-6
    # ~30 GB/s implied DSA-like bandwidth
    assert 20 < m.bandwidth_gbps() < 40


def test_calibration_recovers_linear_model():
    # constants sized well above the host's sleep granularity (containers
    # can have ~1ms timer quanta, which would flatten a microsecond-scale
    # fake model into alpha=0)
    true = LatencyModel(l_fixed_us=1000.0, alpha_us_per_mb=2000.0)

    def fake_transfer(buf):
        time.sleep(true.predict_us(buf.nbytes) * 1e-6)

    m = calibrate(fake_transfer, sizes_bytes=(1 << 19, 1 << 20, 1 << 21),
                  repeats=3)
    assert abs(m.alpha_us_per_mb - 2000.0) < 600.0
    assert m.l_fixed_us < 3000.0


def test_pipeline_depth_from_latency_model():
    m = LatencyModel(10.0, 10.0)
    assert m.pipeline_depth_for(1 << 20, compute_us_per_block=1000.0) == 2
    assert m.pipeline_depth_for(1 << 20, compute_us_per_block=5.0) == 5
    assert m.pipeline_depth_for(1 << 20, compute_us_per_block=0.1) == 8  # cap


# ---------------------------------------------------------------------------
# engine
# ---------------------------------------------------------------------------

def test_engine_sync_mode_never_offloads():
    with AsyncTransferEngine(OffloadPolicy(mode=ExecutionMode.SYNC,
                                           offload_threshold_bytes=1)) as eng:
        job = eng.submit(np.ones((64, 64), np.float32))
        assert job.done()
        assert eng.stats.offloaded == 0 and eng.stats.inline == 1


def test_engine_threshold_keeps_small_transfers_inline():
    pol = OffloadPolicy(mode=ExecutionMode.ASYNC,
                        offload_threshold_bytes=1 << 20)
    with AsyncTransferEngine(pol) as eng:
        eng.submit(np.ones(16, np.float32)).get()          # 64B -> inline
        eng.submit(np.ones(1 << 19, np.float32)).get()     # 2MB -> offload
        assert eng.stats.inline == 1
        assert eng.stats.offloaded == 1


def test_engine_pipelined_backpressure():
    pol = OffloadPolicy(mode=ExecutionMode.PIPELINED, pipeline_depth=2,
                        offload_threshold_bytes=1)
    with AsyncTransferEngine(pol) as eng:
        jobs = [eng.submit(np.full((128,), i, np.float32)) for i in range(6)]
        outs = eng.drain()
        assert len(outs) <= 3                      # ring bounded at depth+1
        vals = [float(np.asarray(j.get())[0]) for j in jobs]
        assert vals == [float(i) for i in range(6)]   # order & values intact


def test_engine_results_correct_across_modes():
    for mode in ExecutionMode:
        with AsyncTransferEngine(OffloadPolicy(mode=mode,
                                               offload_threshold_bytes=1)) as eng:
            x = np.arange(1024, dtype=np.float32)
            out = np.asarray(eng.submit(x).get())
            np.testing.assert_array_equal(out, x)


# ---------------------------------------------------------------------------
# dispatcher / query handler
# ---------------------------------------------------------------------------

def test_dispatcher_sync_returns_directly():
    with RequestDispatcher() as d:
        d.register_handler("inc", lambda x: x + 1)
        assert d.request("inc", np.float32(41), mode="sync") == 42


def test_dispatcher_pipelined_batches():
    pol = OffloadPolicy(mode=ExecutionMode.PIPELINED, max_batch=4)
    with RequestDispatcher(pol, max_batch_wait_s=0.05) as d:
        d.register_handler("sq", lambda x: x * x,
                           batch_fn=lambda xs: [x * x for x in xs])
        jids = [d.request("sq", np.float32(i), mode="pipelined")
                for i in range(8)]
        outs = [d.query(j) for j in jids]
        assert outs == [i * i for i in range(8)]
        assert d.stats.batches < 8                 # some batching happened


def test_dispatcher_async_and_unknown_job():
    with RequestDispatcher() as d:
        d.register_handler("neg", lambda x: -x)
        j = d.request("neg", np.float32(5), mode="async")
        assert d.query(j) == -5
        with pytest.raises(KeyError):
            d.queries.query(99999)


def _registered(job_id=1):
    from repro.core.dispatcher import QueryHandler, Request
    qh = QueryHandler()
    qh.register(Request(job_id, "op", None, ExecutionMode.PIPELINED))
    return qh


def test_query_blocks_until_another_thread_completes():
    import threading
    qh = _registered()
    timer = threading.Timer(0.3, qh.complete, args=(1, "done"))
    timer.start()
    cpu0 = time.thread_time()
    t0 = time.perf_counter()
    assert qh.query(1, timeout=5) == "done"
    assert time.perf_counter() - t0 >= 0.25
    assert time.thread_time() - cpu0 < 0.01     # blocked, did not poll
    assert qh.polls == 1                        # one wait that blocked
    timer.join()
    with pytest.raises(KeyError):               # the job is forgotten
        qh.query(1, timeout=0.01)


def test_query_times_out_at_its_deadline():
    qh = _registered()
    t0 = time.perf_counter()
    with pytest.raises(TimeoutError):
        qh.query(1, timeout=0.2)
    assert 0.2 <= time.perf_counter() - t0 < 0.45
    qh.complete(1, "late")                      # a later wait still gets it
    assert qh.query(1, timeout=0.01) == "late"


# ---------------------------------------------------------------------------
# queue pairs / buffer pools (page-fault-avoidance analogue)
# ---------------------------------------------------------------------------

def test_buffer_pool_reuse():
    pool = BufferPool()
    a = pool.acquire((32, 32), np.float32)
    pool.release(a)
    b = pool.acquire((32, 32), np.float32)
    assert a is b                                   # the same mapping reused
    assert pool.stats.hits == 1 and pool.stats.misses == 1
    c = pool.acquire((32, 32), np.float64)          # different key
    assert c is not a


def test_buffer_pool_preallocate_counts_as_setup():
    pool = BufferPool()
    pool.preallocate((8,), np.float32, 4)
    for _ in range(4):
        pool.release(pool.acquire((8,), np.float32))
    assert pool.stats.misses == 0                   # no runtime page faults
    assert pool.stats.hits >= 4


@given(st.lists(st.sampled_from([(4, 4), (8, 8)]), min_size=1, max_size=12))
def test_buffer_pool_property_reuse_rate(shapes):
    pool = BufferPool(max_per_key=len(shapes))
    held = []
    for s in shapes:
        held.append(pool.acquire(s, np.float32))
    for b in held:
        pool.release(b)
    for s in shapes:
        pool.acquire(s, np.float32)
    assert pool.stats.hits >= len(shapes)           # second pass all hits


def test_queue_pair_slots_and_backpressure():
    qp = QueuePair(2, (4,), (4,))
    s1 = qp.acquire_tx(1)
    s2 = qp.acquire_tx(2)
    assert s1 is not None and s2 is not None
    assert qp.acquire_tx(3) is None                 # ring full
    qp.release(s1)
    assert qp.acquire_tx(3) is not None
