"""repro.ipc: arenas/seqlocks, slot rings, typed channels, real processes.

Single-process tests exercise the shared-memory protocol by opening two
endpoints on one arena (creator + attacher in the same address space — the
memory semantics are identical).  The spawn tests then cross a real process
boundary: producer→consumer byte identity, the mode matrix, seek/restore,
and the dispatcher bridge, all with bounded timeouts.
"""
import multiprocessing as mp
import os
import threading
import time

import numpy as np
import pytest

from repro.core.policy import ExecutionMode, OffloadPolicy
from repro.ipc import (
    ChannelClosed,
    RemoteDispatcherClient,
    Ring,
    RingSpec,
    SeqLock,
    SharedMemoryArena,
    ShmTransport,
    TransportSpec,
    doorbell,
    start_producer,
)

from conftest import wait_until

TIGHT = OffloadPolicy(offload_threshold_bytes=1, poll_interval_us=50.0)
SMALL = TransportSpec(data_slots=3, data_slot_bytes=1 << 20,
                      ctrl_slots=4, ctrl_slot_bytes=4 << 10)


def _pair(spec=SMALL, policy=TIGHT):
    a = ShmTransport.create(spec=spec, policy=policy)
    b = ShmTransport.attach(a.name, policy=policy)
    return a, b


# ---------------------------------------------------------------------------
# arena + seqlock
# ---------------------------------------------------------------------------

def test_arena_create_attach_views():
    a = SharedMemoryArena("rocket-test-arena", size=1 << 16, create=True)
    try:
        b = SharedMemoryArena("rocket-test-arena", create=False)
        arr = a.ndarray(128, (64,), np.int32)
        arr[:] = np.arange(64)
        seen = b.ndarray(128, (64,), np.int32)
        np.testing.assert_array_equal(seen, np.arange(64))
        # control words are shared too
        a.control_words()[7] = 42
        assert int(b.control_words()[7]) == 42
        del arr, seen
        b.close()
    finally:
        a.close()
        a.unlink()


def test_arena_rejects_wrong_magic():
    from multiprocessing import shared_memory
    raw = shared_memory.SharedMemory("rocket-test-bogus", create=True,
                                     size=4096)
    try:
        with pytest.raises(ValueError, match="magic"):
            SharedMemoryArena("rocket-test-bogus", create=False)
    finally:
        raw.close()
        raw.unlink()


def test_seqlock_blocks_torn_reads():
    word = np.zeros(1, np.int64)
    lock = SeqLock(word)
    payload = np.zeros(2, np.int64)

    with lock.write():
        payload[:] = (1, 1)
    assert lock.read(lambda: tuple(payload)) == (1, 1)

    # a reader entering mid-write must not return the half-updated payload
    lock.write_begin()
    payload[0] = 2              # torn state: (2, 1)
    reader_out = {}

    def reader():
        reader_out["v"] = lock.read(lambda: tuple(payload))

    t = threading.Thread(target=reader)
    t.start()
    time.sleep(0.05)
    assert "v" not in reader_out          # still spinning on odd sequence
    payload[1] = 2
    lock.write_end()
    t.join(timeout=5)
    assert reader_out["v"] == (2, 2)


def test_seqlock_retries_on_sequence_change():
    word = np.zeros(1, np.int64)
    lock = SeqLock(word)
    calls = []

    def racy_read():
        calls.append(1)
        if len(calls) == 1:
            # simulate a writer completing a full publish mid-read
            word[0] += 2
        return "ok"

    assert lock.read(racy_read) == "ok"
    assert len(calls) == 2                # first read was discarded as torn


# ---------------------------------------------------------------------------
# rings: acquire/release, wraparound, backpressure
# ---------------------------------------------------------------------------

def _ring_pair(n_slots=3, slot_bytes=4096, policy=TIGHT):
    # a name of this process's own: test workers sharing a host never collide
    arena = SharedMemoryArena(f"rocket-test-ring-{os.getpid()}", size=1 << 20,
                              create=True)
    spec = RingSpec(n_slots, slot_bytes, meta_bytes=128)
    prod = Ring(arena, 0, spec, policy)
    cons = Ring(arena, 0, spec, policy)
    return arena, prod, cons


def test_ring_acquire_release_wraparound():
    arena, prod, cons = _ring_pair(n_slots=3)
    try:
        n_msgs = 10                        # > 3 slots: forces wraparound
        for i in range(n_msgs):
            w = prod.acquire(timeout_s=5)
            w.payload[:8] = np.int64(i).tobytes()
            w.publish(8)
            r = cons.wait_recv(timeout_s=5)
            assert r.seq == i + 1          # seq survives slot reuse
            assert np.frombuffer(r.payload, np.int64)[0] == i
            r.release()
        assert prod.produced == n_msgs
        assert cons.consumed == n_msgs
    finally:
        prod.drop_views(); cons.drop_views()
        arena.close(); arena.unlink()


def test_ring_full_gives_backpressure():
    arena, prod, cons = _ring_pair(n_slots=2)
    try:
        for i in range(2):
            prod.acquire(timeout_s=1).publish(0)
        assert prod.try_acquire() is None              # ring full
        with pytest.raises(TimeoutError):
            prod.acquire(timeout_s=0.2)
        assert prod.stats.full_waits >= 1
        cons.wait_recv(timeout_s=1).release()          # free one slot
        assert prod.try_acquire() is not None
    finally:
        prod.drop_views(); cons.drop_views()
        arena.close(); arena.unlink()


def test_ring_wait_raises_when_peer_closes():
    arena, prod, cons = _ring_pair()
    closed = np.zeros(1, np.int64)
    cons.bind_shutdown_word(closed)
    try:
        t = threading.Timer(0.1, lambda: closed.__setitem__(0, 1))
        t.start()
        with pytest.raises(ChannelClosed):
            cons.wait_recv(timeout_s=10)
        t.join()
    finally:
        prod.drop_views(); cons.drop_views()
        arena.close(); arena.unlink()


# ---------------------------------------------------------------------------
# doorbells: a waiter past its spin window sleeps on a futex, not in naps
# ---------------------------------------------------------------------------

@pytest.fixture(params=["futex", "naps"])
def wait_path(request, monkeypatch):
    """Both ways a ring wait can pass time after its spin window: the
    futex doorbell, and the nap loop kept where futex(2) is missing."""
    if request.param == "naps":
        monkeypatch.setattr(doorbell, "AVAILABLE", False)
    else:
        assert doorbell.AVAILABLE
    return request.param


def _sleepy_consumer_entry(name: str, out_q) -> None:
    """Spawn-child: one blocking wait_recv; report when it returned (the
    host-wide monotonic clock), its thread CPU and its doorbell sleeps."""
    t = ShmTransport.attach(name, policy=TIGHT)
    try:
        rx = t.data.rx
        out_q.put("ready")
        cpu0 = time.thread_time()
        r = rx.wait_recv(timeout_s=5)
        back_ns = time.perf_counter_ns()
        cpu = time.thread_time() - cpu0
        r.release()
        out_q.put((back_ns, cpu, rx.stats.doorbell_sleeps))
    finally:
        t.close()


def test_doorbell_wakes_a_consumer_in_another_process():
    a = ShmTransport.create(spec=SMALL, policy=TIGHT)
    ctx = mp.get_context("spawn")
    out_q = ctx.Queue()
    proc = ctx.Process(target=_sleepy_consumer_entry, args=(a.name, out_q))
    proc.start()
    try:
        assert out_q.get(timeout=120) == "ready"
        time.sleep(0.3)                    # the child is asleep by now
        w = a.data.tx.acquire(timeout_s=1)
        published_ns = time.perf_counter_ns()
        w.publish(0)
        back_ns, cpu_s, sleeps = out_q.get(timeout=30)
        proc.join(timeout=30)
        assert proc.exitcode == 0
        assert (back_ns - published_ns) / 1e9 < 0.05
        assert cpu_s < 0.01                # slept, did not poll
        assert sleeps >= 1
        assert a.data.tx.stats.doorbell_wakes >= 1
    finally:
        if proc.is_alive():
            proc.terminate()
        a.close()


def test_streaming_producer_is_caught_in_the_spin_window():
    """A producer faster than the spin window never sends the consumer to
    the kernel (the spin phase is unchanged)."""
    policy = OffloadPolicy(offload_threshold_bytes=1, spin_us=1e6)
    arena, prod, cons = _ring_pair(n_slots=4, policy=policy)

    def produce():
        for i in range(50):
            w = prod.acquire(timeout_s=5)
            w.payload[:8] = np.int64(i).tobytes()
            w.publish(8)
            time.sleep(0.001)

    t = threading.Thread(target=produce)
    try:
        t.start()
        for i in range(50):
            r = cons.wait_recv(timeout_s=5)
            assert np.frombuffer(r.payload, np.int64)[0] == i
            r.release()
        t.join(timeout=5)
        assert cons.stats.doorbell_sleeps == 0
        assert cons.stats.polls > 0
    finally:
        prod.drop_views(); cons.drop_views()
        arena.close(); arena.unlink()


def test_blocked_acquire_wakes_on_release(wait_path):
    arena, prod, cons = _ring_pair(n_slots=2)
    got = {}

    def blocked_producer():
        w = prod.acquire(timeout_s=5)
        got["t"] = time.perf_counter()
        w.publish(0)

    try:
        for _ in range(2):
            prod.acquire(timeout_s=1).publish(0)
        t = threading.Thread(target=blocked_producer)
        t.start()
        time.sleep(0.3)
        released = time.perf_counter()
        cons.wait_recv(timeout_s=1).release()
        t.join(timeout=5)
        assert got["t"] - released < 0.05
        futex = wait_path == "futex"
        assert (prod.stats.doorbell_sleeps >= 1) is futex
        assert (cons.stats.doorbell_wakes >= 1) is futex
    finally:
        prod.drop_views(); cons.drop_views()
        arena.close(); arena.unlink()


def test_peer_close_wakes_a_sleeping_waiter(wait_path):
    """close() rings the doorbells the peer sleeps on: ChannelClosed comes
    at once, not at the end of a sleep slice."""
    a, b = _pair()
    out = {}

    def waiter():
        try:
            b.data.rx.wait_recv(timeout_s=10)
        except ChannelClosed:
            out["t"] = time.perf_counter()

    try:
        t = threading.Thread(target=waiter)
        t.start()
        time.sleep(0.15)                   # asleep, well inside a slice
        closed = time.perf_counter()
        a.close()
        t.join(timeout=10)
        assert out["t"] - closed < 0.2
    finally:
        a.close()
        b.close()


def test_ring_wait_times_out_without_a_publish(wait_path):
    arena, prod, cons = _ring_pair()
    try:
        t0 = time.perf_counter()
        with pytest.raises(TimeoutError):
            cons.wait_recv(timeout_s=0.3)
        assert 0.3 <= time.perf_counter() - t0 < 0.55
    finally:
        prod.drop_views(); cons.drop_views()
        arena.close(); arena.unlink()


# no spin window: every wait that is not satisfied at once goes to its
# doorbell (or its naps), so a lost wake-up would leave a whole slice
NO_SPIN = OffloadPolicy(offload_threshold_bytes=1, spin_us=0.0)
PING_PONGS = 5000


def _echo_entry(name: str, futex: bool, out_q) -> None:
    """Spawn-child: send back every message, in order, PING_PONGS times;
    report the longest wait after the first."""
    doorbell.AVAILABLE = futex and doorbell.AVAILABLE
    t = ShmTransport.attach(name, policy=NO_SPIN)
    longest = 0.0
    try:
        rx, tx = t.data.rx, t.data.tx
        for i in range(PING_PONGS):
            t0 = time.perf_counter()
            r = rx.wait_recv(timeout_s=30)
            if i:
                longest = max(longest, time.perf_counter() - t0)
            value = bytes(r.payload[:8])
            r.release()
            w = tx.acquire(timeout_s=30)
            w.payload[:8] = value
            w.publish(8)
        out_q.put(longest)
    finally:
        t.close()


def test_two_process_ping_pong_loses_no_wakeup(wait_path):
    a = ShmTransport.create(spec=SMALL, policy=NO_SPIN)
    ctx = mp.get_context("spawn")
    out_q = ctx.Queue()
    proc = ctx.Process(target=_echo_entry,
                       args=(a.name, wait_path == "futex", out_q))
    proc.start()
    try:
        rx, tx = a.data.rx, a.data.tx
        longest = 0.0
        for i in range(PING_PONGS):
            w = tx.acquire(timeout_s=30)
            w.payload[:8] = np.int64(i).tobytes()
            w.publish(8)
            t0 = time.perf_counter()
            r = rx.wait_recv(timeout_s=120 if i == 0 else 30)
            if i:
                longest = max(longest, time.perf_counter() - t0)
            assert np.frombuffer(r.payload, np.int64)[0] == i
            r.release()
        child_longest = out_q.get(timeout=60)
        proc.join(timeout=30)
        assert proc.exitcode == 0
        assert longest < 0.25 and child_longest < 0.25
        if wait_path == "futex":
            assert rx.stats.doorbell_sleeps > 0
            assert tx.stats.doorbell_wakes > 0
    finally:
        if proc.is_alive():
            proc.terminate()
        a.close()


# ---------------------------------------------------------------------------
# channels: mode matrix, zero copy, size guards (in-process pair)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("mode", ["sync", "async", "pipelined"])
def test_channel_mode_matrix(mode):
    policy = OffloadPolicy(mode=ExecutionMode(mode), offload_threshold_bytes=1,
                           pipeline_depth=2)
    a, b = _pair(policy=policy)
    try:
        trees = [{"x": np.full((2048,), i, np.int64),
                  "nested": {"y": np.float32(i) * np.ones((3, 5), np.float32)}}
                 for i in range(7)]
        recvd = []

        def consume():
            for _ in trees:
                tree, header = b.recv(timeout_s=20)
                recvd.append((tree, header))

        t = threading.Thread(target=consume)
        t.start()
        handles = [a.send(tr, header={"i": i}) for i, tr in enumerate(trees)]
        for h in handles:
            h.wait(timeout_s=20)
        a.data.flush(timeout_s=20)
        t.join(timeout=30)
        assert not t.is_alive()
        assert [h["i"] for _, h in recvd] == list(range(7))  # FIFO survives
        for i, (tree, _) in enumerate(recvd):
            np.testing.assert_array_equal(tree["x"], trees[i]["x"])
            np.testing.assert_array_equal(tree["nested"]["y"],
                                          trees[i]["nested"]["y"])
        if mode == "sync":
            assert a.data.stats.offloaded == 0
        else:
            assert a.data.stats.offloaded == 7
    finally:
        b.close(); a.close()


def test_channel_zero_copy_views():
    a, b = _pair()
    try:
        payload = {"x": np.arange(4096, dtype=np.int32)}
        a.send(payload, mode="sync")
        lease = b.recv(copy=False)
        assert lease.tree["x"].base is not None        # a view, not a copy
        np.testing.assert_array_equal(lease.tree["x"], payload["x"])
        lease.release()
        assert lease.tree is None                      # views dropped
    finally:
        b.close(); a.close()


def test_channel_oversize_message_raises_without_heap():
    """With the bulk heap disabled, slot capacity is still a hard cap."""
    import dataclasses
    a, b = _pair(spec=dataclasses.replace(SMALL, heap_extents=0))
    try:
        with pytest.raises(ValueError, match="slot capacity"):
            a.send({"x": np.zeros(SMALL.data_slot_bytes + 1, np.uint8)},
                   mode="sync")
    finally:
        b.close(); a.close()


def test_channel_oversize_message_rides_the_heap():
    """The same over-slot message on a heap-enabled transport (the
    default spec) goes through: the ring carries only the extent
    descriptor and the payload round-trips byte-identically."""
    a, b = _pair()
    try:
        msg = {"x": np.arange(SMALL.data_slot_bytes + 1, dtype=np.uint8)}
        a.send(msg, mode="sync")
        tree, _ = b.recv(timeout_s=10)
        np.testing.assert_array_equal(tree["x"], msg["x"])
        assert a.data.stats.heap_sends == 1
        assert b.data.stats.heap_recvs == 1
    finally:
        b.close(); a.close()


@pytest.mark.parametrize("mode", ["sync", "async", "pipelined"])
def test_spawn_heap_messages_byte_identical(mode):
    """Large (heap-routed) batches from a producer *process* arrive
    byte-identical in every send mode, interleaved with small slot-path
    messages (the mark leaf stays tiny; tokens exceed the slot)."""
    policy = OffloadPolicy(mode=ExecutionMode(mode),
                           offload_threshold_bytes=1,
                           heap_threshold_bytes=1 << 19,
                           heap_chunk_bytes=1 << 19)
    handle = start_producer(_counting_spec(seed=11), policy=policy,
                            spec=SMALL, n_batches=4)
    try:
        ref = make_counting_source(seed=11)
        for i in range(4):
            batch, header = handle.recv_batch(timeout_s=60)
            expect = next(ref)
            assert header["step"] == i
            for k in expect:
                assert batch[k].tobytes() == expect[k].tobytes()
        _, header = handle.recv_batch(timeout_s=60)
        assert header.get("eof")
        # tokens are 64*1024*8 B = 512 KB >= heap threshold: heap-routed
        assert handle.transport.data.stats.heap_recvs == 4
    finally:
        handle.stop()
    assert handle.process.exitcode == 0


def test_control_channel_roundtrip():
    a, b = _pair()
    try:
        a.send_msg({"cmd": "seek", "step": 3})
        assert b.recv_msg(timeout_s=5) == {"cmd": "seek", "step": 3}
        assert b.ctrl.try_recv_msg() is None
    finally:
        b.close(); a.close()


def test_transport_geometry_from_descriptor():
    """The attacher learns ring geometry from the arena, not from args."""
    spec = TransportSpec(data_slots=5, data_slot_bytes=1 << 18,
                         ctrl_slots=3, ctrl_slot_bytes=1 << 12)
    a = ShmTransport.create(spec=spec)
    b = ShmTransport.attach(a.name)
    try:
        assert b.spec == spec
        assert b.data.rx.spec.n_slots == 5
    finally:
        b.close(); a.close()


# ---------------------------------------------------------------------------
# real process boundary (spawn)
# ---------------------------------------------------------------------------

def make_counting_source(seed=0, rows=64, cols=1024):
    """Deterministic numpy-only source (spawn-importable from this module)."""

    class CountingSource:
        def __init__(self):
            self.seed, self.step = seed, 0

        def state(self):
            return {"seed": self.seed, "step": self.step}

        def restore(self, st):
            self.seed, self.step = int(st["seed"]), int(st["step"])

        def __iter__(self):
            return self

        def __next__(self):
            rng = np.random.default_rng((self.seed, self.step))
            self.step += 1
            return {"tokens": rng.integers(0, 1 << 30, (rows, cols),
                                           dtype=np.int64),
                    "mark": np.full((4,), self.step - 1, np.int32)}

    return CountingSource()


def _counting_spec(seed=0):
    return {"kind": "factory", "path": "test_ipc:make_counting_source",
            "kwargs": {"seed": seed}}


@pytest.mark.parametrize("mode", ["sync", "async", "pipelined"])
def test_spawn_producer_consumer_byte_identical(mode):
    policy = OffloadPolicy(mode=ExecutionMode(mode), offload_threshold_bytes=1)
    handle = start_producer(_counting_spec(seed=9), policy=policy,
                            spec=SMALL, n_batches=6)
    try:
        ref = make_counting_source(seed=9)
        for i in range(6):
            batch, header = handle.recv_batch(timeout_s=60)
            expect = next(ref)
            assert header["step"] == i
            for k in expect:
                assert batch[k].tobytes() == expect[k].tobytes()   # bytes!
        _, header = handle.recv_batch(timeout_s=60)
        assert header.get("eof")
    finally:
        handle.stop()
    assert handle.process.exitcode == 0


def test_spawn_producer_seek_restores_stream():
    handle = start_producer(_counting_spec(seed=4), spec=SMALL,
                            policy=TIGHT, n_batches=None)
    try:
        for i in range(3):
            batch, header = handle.recv_batch(timeout_s=60)
            assert header["step"] == i
        gen = handle.seek(1)
        ref = make_counting_source(seed=4)
        ref.restore({"seed": 4, "step": 1})
        expect = next(ref)
        # drain stale in-flight batches (old generation), then verify replay;
        # a stale slot may even carry step==1, so the gen check is the gate
        deadline = time.perf_counter() + 60
        while True:
            batch, header = handle.recv_batch(timeout_s=60)
            if header.get("gen") == gen and header.get("step") == 1:
                break
            assert time.perf_counter() < deadline
        np.testing.assert_array_equal(batch["tokens"], expect["tokens"])
    finally:
        handle.stop()


def test_spawn_producer_seek_after_eof_restarts_stream():
    """restore() on a finished finite stream must restart production,
    not strand the consumer until the producer's linger expires."""
    handle = start_producer(_counting_spec(seed=2), spec=SMALL,
                            policy=TIGHT, n_batches=2)
    try:
        for _ in range(2):
            handle.recv_batch(timeout_s=60)
        _, header = handle.recv_batch(timeout_s=60)
        assert header.get("eof")
        gen = handle.seek(0)
        expect = next(make_counting_source(seed=2))
        deadline = time.perf_counter() + 60
        while True:
            batch, header = handle.recv_batch(timeout_s=60)
            if header.get("gen") == gen and header.get("step") == 0:
                break
            assert time.perf_counter() < deadline
        np.testing.assert_array_equal(batch["tokens"], expect["tokens"])
    finally:
        handle.stop()


def test_spawn_consumer_close_unblocks_producer():
    """Producer blocked on a full ring must exit on close, not deadlock."""
    handle = start_producer(_counting_spec(), spec=SMALL,
                            policy=TIGHT, n_batches=None)
    try:
        handle.recv_batch(timeout_s=60)        # producer is alive + streaming
        rx = handle.transport.data.rx
        wait_until(lambda: rx.produced - rx.consumed >= rx.spec.n_slots,
                   10, desc="producer to fill the data ring")
    finally:
        t0 = time.perf_counter()
        handle.stop(timeout_s=15)
    assert time.perf_counter() - t0 < 15, "producer had to be terminated"
    assert not handle.process.is_alive()


# -- dispatcher bridge --------------------------------------------------------

def _rpc_client_entry(name: str) -> None:
    policy = OffloadPolicy(offload_threshold_bytes=1)
    t = ShmTransport.attach(name, policy=policy)
    client = RemoteDispatcherClient(t)
    out = client.request("double", np.arange(16, dtype=np.float32),
                         mode="sync")
    np.testing.assert_array_equal(out, 2 * np.arange(16, dtype=np.float32))
    jids = [client.request("double", np.full((512,), i, np.float32), mode=m)
            for i, m in enumerate(["async", "pipelined", "pipelined"])]
    for i, jid in reversed(list(enumerate(jids))):     # out-of-order queries
        assert float(client.query(jid, timeout=30)[0]) == 2.0 * i
    with pytest.raises(RuntimeError, match="KeyError"):
        client.request("no-such-op", np.zeros(4), mode="sync")
    client.close()
    t.close()


def test_remote_dispatcher_across_processes():
    from repro.core.dispatcher import RequestDispatcher
    from repro.ipc import DispatcherServer

    policy = OffloadPolicy(offload_threshold_bytes=1)
    transport = ShmTransport.create(spec=SMALL, policy=policy)
    dispatcher = RequestDispatcher(policy)
    dispatcher.register_handler("double", lambda x: x * 2,
                                batch_fn=lambda xs: [x * 2 for x in xs])
    server = DispatcherServer(dispatcher, transport).start()
    proc = mp.get_context("spawn").Process(target=_rpc_client_entry,
                                           args=(transport.name,))
    proc.start()
    proc.join(timeout=120)
    try:
        assert proc.exitcode == 0
        assert dispatcher.stats.requests >= 4
    finally:
        server.close()
        dispatcher.close()
        transport.close()


# -- acceptance: pipeline determinism across the process boundary -------------

@pytest.mark.slow
def test_input_pipeline_ipc_matches_in_process_source():
    """InputPipeline fed by an IPC producer process yields batches identical
    to the in-process SyntheticLMSource for the same seed."""
    from repro.configs import get_smoke_config
    from repro.configs.base import ShapeConfig
    from repro.data import InputPipeline, SyntheticLMSource, make_source

    cfg = get_smoke_config("granite-8b")
    shape = ShapeConfig("ipc-test", "train", 8, 32)
    policy = OffloadPolicy(mode=ExecutionMode.PIPELINED,
                           offload_threshold_bytes=1)
    src = make_source(cfg, shape, source="ipc", seed=123, policy=policy)
    pipe = InputPipeline(src, policy)
    ref = InputPipeline(SyntheticLMSource(cfg, shape, seed=123), policy)
    try:
        for _ in range(4):
            got, expect = next(pipe), next(ref)
            assert set(got) == set(expect)
            for k in expect:
                np.testing.assert_array_equal(np.asarray(got[k]),
                                              np.asarray(expect[k]))
    finally:
        pipe.close()
        ref.close()
