"""Warm-standby failover tests: diskless replication, promotion, reclaim.

Covers the failover plane end to end, in-process where possible (the
replication datapath — manifest/shard/delta pulls, CRC containment and
re-pull, delta-log exactly-once import, byte-identical restore) and in
spawned supervised processes for the headline drills (kill the primary
mid-snapshot under load → promotion preserves exactly-once; a stalled
promotion falls back to a cold restart).  Also pins the supervisor's
reclaim dot-boundary (a sibling fabric whose name merely extends ours
must survive), the dead-rendezvous ALIVE-word fail-fast, and the typed
:class:`~repro.ipc.worker.ReconnectTimeout` deadline bound.
"""
from __future__ import annotations

import os
import signal
import threading
import time
from multiprocessing import shared_memory

import numpy as np
import pytest
from conftest import wait_until

from repro.core.dispatcher import RequestDispatcher
from repro.core.policy import OffloadPolicy, RetryPolicy
from repro.ft import inject
from repro.ft.inject import FaultPlane, FaultSpec
from repro.ft.standby import StandbyReplica, _cold_params, param_echo_factory
from repro.ft.supervisor import (SHM_DIR, FabricSupervisor,
                                 _mark_rendezvous_dead, reclaim_segments)
from repro.ipc.listener import Listener, connect as listener_connect
from repro.ipc.transport import TransportSpec
from repro.ipc.worker import (ReconnectTimeout, RemoteDispatcherClient,
                              ServingFabric)

FAST = RetryPolicy(heartbeat_interval_s=0.05, heartbeat_stale_s=0.3,
                   connect_timeout_s=5.0, max_reconnects=6)
POL = OffloadPolicy(mode="pipelined", retry=FAST)
SMALL = TransportSpec(data_slots=8, data_slot_bytes=1 << 16,
                      heap_extent_bytes=1 << 16, heap_extents=8)
FACTORY = "repro.ft.standby:param_echo_factory"


@pytest.fixture(autouse=True)
def _no_leftover_plane():
    inject.uninstall()
    yield
    inject.uninstall()


def _name(tag: str) -> str:
    return f"rocket-{tag}-{os.getpid()}"


# ---------------------------------------------------------------------------
# in-process replication datapath
# ---------------------------------------------------------------------------

def test_standby_mirrors_primary_byte_identical_and_restores():
    """One sync round mirrors the full snapshot (CRC-gated shards +
    delta) byte-identically, and a factory restore from that state
    serves the identical params (psum witness + digest)."""
    name = _name("fo-sync")
    fab = param_echo_factory(name, POL)
    try:
        replica = StandbyReplica(name, POL, interval_s=0.05)
        try:
            assert replica.sync_once()
        finally:
            replica.close()
    finally:
        fab.close()
    st = replica.state()
    assert st["seq"] == 1
    assert replica.stats["snapshots_applied"] == 1
    assert replica.stats["shard_pulls"] == len(st["manifest"]["sizes"])
    cold = _cold_params()
    for k, w in cold["layers"].items():
        got = st["tree"]["layers"][k]
        assert got.dtype == w.dtype and np.array_equal(got, w)

    fab2 = param_echo_factory(_name("fo-restored"), POL, state=st)
    try:
        cli = RemoteDispatcherClient.connect(fab2.name, policy=POL)
        try:
            expect = sum(float(w.sum()) for w in cold["layers"].values())
            assert float(cli.request("psum", np.zeros(1),
                                     mode="sync")) == expect
        finally:
            cli.close()
        # the restored source re-serves the same payload bytes
        assert (fab2.replication.snapshot_now()["digest"]
                == st["manifest"]["digest"])
    finally:
        fab2.close()


def test_shard_corruption_contained_by_crc_and_repulled():
    """``ckpt.shard.corrupt`` damages pulled shards; the replica's CRC
    gate catches each one and re-pulls only that shard — the applied
    snapshot is still byte-identical."""
    inject.install(FaultPlane(3, {
        "ckpt.shard.corrupt": FaultSpec(at=(1, 5))}))
    name = _name("fo-crc")
    fab = param_echo_factory(name, POL)
    try:
        replica = StandbyReplica(name, POL, interval_s=0.05)
        try:
            assert replica.sync_once()
        finally:
            replica.close()
        assert replica.stats["shard_corrupt"] == 2
        assert replica.stats["snapshots_applied"] == 1
        # two damaged pulls cost exactly two extra shard requests
        n = len(replica.state()["manifest"]["sizes"])
        assert replica.stats["shard_pulls"] == n + 2
        assert (replica.state()["manifest"]["digest"]
                == fab.replication._manifest["digest"])
    finally:
        fab.close()


def test_standby_lag_site_skips_sync_rounds():
    inject.install(FaultPlane(4, {
        "standby.lag": FaultSpec(rate=1.0, max_fires=2, stall_s=0.01)}))
    name = _name("fo-lag")
    fab = param_echo_factory(name, POL)
    try:
        replica = StandbyReplica(name, POL, interval_s=0.02)
        stop = threading.Event()
        t = threading.Thread(target=replica.run, args=(stop,), daemon=True)
        t.start()
        try:
            wait_until(lambda: replica.stats["lag_skips"] == 2
                       and replica.stats["syncs"] >= 1,
                       desc="lag skips then sync")
        finally:
            stop.set()
            t.join(timeout=10.0)
    finally:
        fab.close()
    assert replica.lag_ms() < float("inf")


def test_dispatcher_delta_import_preserves_exactly_once():
    """The delta log (export_state → import_state) carries settled dedup
    entries across a promotion: a replayed request on the importing
    dispatcher is answered from the window, never re-executed."""
    calls: list = []
    d1 = RequestDispatcher(POL)
    d1.register_handler("inc", lambda x: calls.append(1) or x + 1)
    first: list = []
    d1.submit_many([{"op": "inc", "data": np.arange(4.0), "dedup": 99,
                     "mode": "async",
                     "on_complete": lambda _j, r: first.append(r)}])
    wait_until(lambda: first, desc="original reply")
    out = first[0]
    delta = d1.export_state()
    d1.close()
    assert calls == [1]

    d2 = RequestDispatcher(POL)
    d2.register_handler("inc", lambda x: calls.append(2) or x + 1)
    landed = d2.import_state(delta)
    assert landed["dedup_entries"] >= 1
    replayed: list = []
    jids = d2.submit_many([{"op": "inc", "data": np.arange(4.0),
                            "dedup": 99, "mode": "async",
                            "on_complete":
                                lambda _j, r: replayed.append(r)}])
    assert jids == [-1]                   # resolved from the window
    wait_until(lambda: replayed, desc="replayed reply")
    assert np.array_equal(replayed[0], out)
    d2.close()
    assert calls == [1]                   # never re-executed


# ---------------------------------------------------------------------------
# supervisor reclaim + failure-detection edges
# ---------------------------------------------------------------------------

def test_reclaim_respects_dot_boundary_and_zeroes_alive_word():
    """Reclaim takes the exact name + ``name.``-prefixed segments only —
    a sibling fabric whose name merely extends ours survives — and
    zeroes the dead rendezvous ALIVE word before unlinking, which
    surviving mappings observe."""
    base = _name("rcl")
    segs = {n: shared_memory.SharedMemory(name=n, create=True, size=256)
            for n in (base, f"{base}.c0-1", f"{base}.c0-1.h")}
    sibling = shared_memory.SharedMemory(name=base + "x", create=True,
                                         size=256)
    try:
        segs[base].buf[64:72] = b"\x01" * 8       # "alive"
        counts = reclaim_segments(base)
        assert counts == {"arenas": 2, "heaps": 1}
        # the surviving mapping sees the fail-fast word flip
        assert bytes(segs[base].buf[64:72]) == b"\x00" * 8
        assert os.path.exists(os.path.join(SHM_DIR, base + "x"))
        assert not os.path.exists(os.path.join(SHM_DIR, base))
        assert not os.path.exists(os.path.join(SHM_DIR, f"{base}.c0-1.h"))
    finally:
        for seg in segs.values():
            seg.close()
            try:
                seg.unlink()              # already reclaimed: unregister
            except FileNotFoundError:
                pass
        sibling.close()
        sibling.unlink()


def test_connect_fails_fast_on_dead_rendezvous():
    """A client arriving at (or caught mid-registration in) a rendezvous
    whose owner died fails in milliseconds on the zeroed ALIVE word
    instead of burning its whole connect timeout."""
    with Listener(None, SMALL, POL) as lsn:     # never started: no ACKs
        _mark_rendezvous_dead(lsn.name)
        t0 = time.perf_counter()
        with pytest.raises(ConnectionError):
            listener_connect(lsn.name, policy=POL, timeout_s=30.0)
        assert time.perf_counter() - t0 < 5.0


def test_reconnect_deadline_raises_typed_error():
    """``reconnect(deadline=...)`` bounds the cumulative backoff by the
    caller's budget and raises :class:`ReconnectTimeout` — catchable as
    either ConnectionError or TimeoutError."""
    d = RequestDispatcher(POL)
    d.register_handler("echo", lambda x: x)
    fab = ServingFabric(d, spec=SMALL, policy=POL,
                        own_dispatcher=True).start()
    cli = None
    try:
        cli = RemoteDispatcherClient.connect(fab.name, policy=POL)
        assert cli.request("echo", np.arange(3),
                           mode="sync").tolist() == [0, 1, 2]
    finally:
        fab.close()                       # server gone, name unlinked
    try:
        t0 = time.perf_counter()
        with pytest.raises(ReconnectTimeout) as ei:
            cli.reconnect(deadline=time.perf_counter() + 0.5)
        assert time.perf_counter() - t0 < 5.0
        assert isinstance(ei.value, ConnectionError)
        assert isinstance(ei.value, TimeoutError)
    finally:
        cli.close()


# ---------------------------------------------------------------------------
# spawned drills
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_warm_failover_mid_snapshot_preserves_exactly_once():
    """Headline: SIGKILL the primary mid-replication under client load;
    the supervisor promotes the warm standby under the same rendezvous
    name and the client rides through with zero lost, zero duplicated
    replies and byte-identical state."""
    name = _name("fo-soak")
    sup = FabricSupervisor(name, FACTORY, policy=POL, max_restarts=2,
                           standby_factory=FACTORY,
                           standby_interval_s=0.05,
                           promote_timeout_s=20.0).start()
    try:
        assert sup.wait_alive(30.0)
        cli = RemoteDispatcherClient.connect(name, policy=POL,
                                             timeout_s=30.0)
        try:
            wait_until(lambda: (sup.standby_stats(timeout_s=5.0) or {})
                       .get("snapshots_applied", 0) >= 1,
                       timeout_s=60.0, desc="first applied snapshot")
            expect = sum(float(w.sum())
                         for w in _cold_params()["layers"].values())
            assert float(cli.request("psum", np.zeros(1),
                                     mode="sync")) == expect
            vec = np.arange(32, dtype=np.float64)
            for i in range(24):
                if i == 8:          # standby syncs at 50ms: mid-snapshot
                    os.kill(sup._proc.pid, signal.SIGKILL)
                out = cli.request("double", vec + i, mode="sync")
                assert np.array_equal(out, (vec + i) * 2), f"request {i}"
            # promoted state is the primary's, byte-identical
            assert float(cli.request("psum", np.zeros(1),
                                     mode="sync")) == expect
            assert cli.reconnects >= 1
            assert cli.lost_replies == 0 and cli.dup_replies == 0
            assert not cli._unacked
        finally:
            cli.close()
        s = sup.stats()
        assert s["crashes"] == 1
        assert s["promotions"] == 1 and s["restarts"] == 0
        assert s["last_promotion"]["seq"] >= 1
        assert s["last_promotion"]["digest"]
        assert s["state"] == "running" and s["standby_alive"]
    finally:
        sup.close()
    assert [f for f in os.listdir(SHM_DIR) if f.startswith(name)] == []


@pytest.mark.slow
def test_stalled_promotion_falls_back_to_cold_restart():
    """``standby.promote.stall`` wedges the promotion past the
    supervisor's timeout: the standby is killed (it must never race the
    replacement for the rendezvous), a cold restart recovers, and the
    client still completes every request exactly once."""
    name = _name("fo-stall")
    crash = FaultPlane(9, {"worker.crash": FaultSpec(at=(3,))})
    stall = FaultPlane(9, {"standby.promote.stall":
                           FaultSpec(rate=1.0, max_fires=1, stall_s=10.0)})
    sup = FabricSupervisor(name, FACTORY, policy=POL, max_restarts=2,
                           plane_json=crash.spec_json(),
                           standby_factory=FACTORY,
                           standby_interval_s=0.05,
                           promote_timeout_s=0.5,
                           standby_plane_json=stall.spec_json()).start()
    try:
        assert sup.wait_alive(30.0)
        cli = RemoteDispatcherClient.connect(name, policy=POL,
                                             timeout_s=30.0)
        try:
            vec = np.arange(16, dtype=np.int64)
            for i in range(8):
                out = cli.request("double", vec + i, mode="sync")
                assert np.array_equal(out, (vec + i) * 2), f"request {i}"
            assert cli.reconnects >= 1
            assert cli.lost_replies == 0 and cli.dup_replies == 0
        finally:
            cli.close()
        s = sup.stats()
        assert s["crashes"] == 1
        assert s["promote_stalls"] == 1 and s["promotions"] == 0
        assert s["restarts"] == 1          # the cold fallback
        assert s["state"] == "running"
    finally:
        sup.close()
    assert [f for f in os.listdir(SHM_DIR) if f.startswith(name)] == []


SUPERVISOR_PARENT = """
import os, signal, sys
import numpy as np
from jax._src import xla_bridge
from repro.core.policy import OffloadPolicy, RetryPolicy
from repro.ft.supervisor import FabricSupervisor
from repro.ipc.worker import RemoteDispatcherClient

name, factory = sys.argv[1], sys.argv[2]
pol = OffloadPolicy(mode="pipelined", retry=RetryPolicy(
    heartbeat_interval_s=0.05, heartbeat_stale_s=0.3,
    connect_timeout_s=5.0, max_reconnects=6))
sup = FabricSupervisor(name, factory, policy=pol, max_restarts=2,
                       standby_factory=factory, standby_interval_s=0.05,
                       promote_timeout_s=20.0).start()
try:
    assert sup.wait_alive(30.0)
    cli = RemoteDispatcherClient.connect(name, policy=pol, timeout_s=30.0)
    try:
        vec = np.arange(8, dtype=np.float64)
        for i in range(6):
            if i == 3:
                os.kill(sup._proc.pid, signal.SIGKILL)
            assert np.array_equal(cli.request("double", vec + i, mode="sync"),
                                  (vec + i) * 2)
    finally:
        cli.close()
    s = sup.stats()
    assert s["crashes"] == 1 and s["promotions"] + s["restarts"] == 1, s
finally:
    sup.close()
print("BACKENDS_INITIALIZED", xla_bridge.backends_are_initialized())
"""


@pytest.mark.slow
def test_supervisor_parent_never_initializes_a_jax_backend():
    """The supervisor's children take the chip, so the supervising parent
    (spawn, watch, promote or restart, close) must never initialize a JAX
    backend itself: a fresh interpreter drives a crash + recovery and
    reports its own backend state."""
    import subprocess
    import sys
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=os.path.join(root, "src"))
    out = subprocess.run(
        [sys.executable, "-c", SUPERVISOR_PARENT, _name("fo-nojax"), FACTORY],
        capture_output=True, text=True, timeout=240, env=env, cwd=root)
    assert out.returncode == 0, out.stdout[-2000:] + out.stderr[-2000:]
    assert "BACKENDS_INITIALIZED False" in out.stdout, out.stdout[-2000:]
