"""Reactor: one server thread multiplexing N client transports fairly.

The serving side of the multi-client fabric.  One thread sweeps all
registered connections round-robin, draining at most
``max_drain_per_sweep`` messages from each per pass, so a chatty client
cannot monopolize the sweep; a per-connection ``max_inflight`` admission
cap stops a flooding client from stuffing the shared dispatcher queue —
once its replies lag, its requests stay in its *own* ring and the ring's
bounded depth backpressures the sender (the paper's bounded queue pairs,
now doing double duty as a fairness mechanism).  Replies — which run on
the shared dispatcher worker — use a short timeout, and a timed-out or
closed reply path marks the connection dead for reaping, so a vanished
client costs one bounded stall rather than a 30s head-of-line block per
outstanding reply.

**Batched drain**: each poll iteration pulls *all* ready messages from a
connection in one ``try_recv_many`` sweep (bounded by the fairness
quantum and the admission cap) — a client's coalesced frame of K
sub-messages costs one ring poll and one ``on_messages`` handoff into
batch formation, not K callback iterations.

**Lane-ordered sweep** (SLO serving): each connection remembers the most
urgent priority class its last drain saw (the wire's reserved
:data:`~repro.ipc.channel.PRIO_KEY` header), and every sweep visits
connections sorted ``(lane, cid)`` — a priority-0 client's ring is
drained before best-effort lanes under the same per-connection quantum,
so lane ordering holds end to end (wire → drain → dispatcher heap)
without starving anyone: the quantum and admission caps are unchanged.

**Zero-copy drain** (default, ``policy.zero_copy_serving``): requests are
received as :class:`~repro.ipc.channel.RecvLease` views into the shared
slot — no receive-side staging copy — and handed to ``on_message`` still
leased; the consumer (the fabric → dispatcher) releases each lease once
the payload has been gathered into a batch buffer.  A held lease keeps
its ring slot occupied, so the ring depth bounds how far a client can run
ahead of batch formation (backpressure, not a copy).  With
``zero_copy_serving=False`` the reactor copies each payload out
immediately (the pre-CopyEngine datapath, kept for A/B measurement) and
delivers a pre-released lease.

Replies go back **reserve-then-fill**: :meth:`Connection.reply` claims
the client's tx slot first and packs the result array straight into it
(one counted memcpy, no staging tree, descriptor meta from the channel's
structure cache).

Idle behaviour is the repo-wide hybrid policy: after an empty sweep the
reactor spins (yield-only) for ``policy.spin_us`` so a streaming client is
picked up at memcpy latency, then falls back to ``poll_interval_us``
quantum sleeps — the UMWAIT analogue, now amortized over *all* clients
instead of one blocking ``recv`` per connection.

Disconnects are part of the sweep: a connection whose peer raised its
closed flag (and whose ring is fully drained) is reaped — leaked
bulk-heap extents force-freed (``stats.heap_reaped``), its transport
closed, its arena and heap segment unlinked — and reported through
``on_disconnect``, so client churn cannot leak arenas or heap.

Large requests arrive exactly like small ones: the channel resolves a
heap-routed message into extent-backed views, so the lease handed to
``on_message`` is zero-copy either way, and the dispatcher's release
after batch gather is also what frees the extents (lease-based
reclamation).
"""
from __future__ import annotations

import threading
import time
from dataclasses import dataclass, field
from typing import Callable, Optional

import numpy as np

from repro.core.copyengine import SGList, get_engine
from repro.core.policy import OffloadPolicy
from repro.ft import inject as _inject
from repro.ipc.channel import PRIO_KEY, RecvLease
from repro.ipc.ring import ChannelClosed
from repro.ipc.transport import ShmTransport
from repro.obs import hwcounters as _hw
from repro.obs import trace as _trace


def _lease_bytes(items) -> int:
    """Total payload bytes of one drain pull (profiling only — called
    behind the ``PROF.enabled`` guard, never on the undisturbed path)."""
    total = 0
    for item in items:
        tree = item.tree if isinstance(item, RecvLease) else item[0]
        if isinstance(tree, dict):
            for v in tree.values():
                total += getattr(v, "nbytes", 0)
        else:
            total += getattr(tree, "nbytes", 0)
    return total


@dataclass
class Connection:
    """One registered client: its transport plus fairness accounting."""
    cid: int
    transport: ShmTransport
    received: int = 0          # messages drained from this client
    replied: int = 0           # replies sent back to this client
    inflight: int = 0          # dispatched, reply not yet sent (admission cap)
    dead: bool = False         # reply path failed: reap at the next sweep
    lane: int = 0              # SLO lane: last priority class seen on this
                               # client's wire (sweep visits low lanes first)
    _lock: threading.Lock = field(default_factory=threading.Lock, repr=False)

    def begin(self) -> None:
        """Count one message as dispatched (reactor thread)."""
        with self._lock:
            self.received += 1
            self.inflight += 1

    def done(self) -> None:
        """Count one reply as sent (any completion thread)."""
        with self._lock:
            self.replied += 1
            self.inflight -= 1

    def reply(self, tree, header: dict,
              timeout_s: Optional[float] = None) -> None:
        """Send a reply on this client's transport and settle accounting.

        A reply whose payload is a single ``result`` array takes the
        reserve-then-fill fast path: the destination tx slot is claimed
        first and the array packed straight into it (one counted memcpy,
        no staging tree, no per-send descriptor pickle).  Anything else
        (error replies, odd shapes) falls back to a plain sync send.

        The timeout (default ``policy.retry.reply_timeout_s``) is
        deliberately short and a failure marks the connection dead:
        replies run on the *shared* dispatcher worker thread, so a
        vanished client whose reply ring filled up must cost at most one
        bounded stall — not a 30s head-of-line block per reply while
        every other client starves.
        """
        if timeout_s is None:
            timeout_s = self.transport.policy.retry.reply_timeout_s
        if _inject._PLANE is not None:
            _inject.stall("reactor.reply.stall")
        t0 = _trace.now() if _trace.TRACE.enabled else 0
        c0 = _hw.begin() if _hw.PROF.enabled else None
        try:
            arr = tree.get("result") if isinstance(tree, dict) else None
            if (isinstance(arr, np.ndarray) and len(tree) == 1):
                slot = self.transport.data.reserve(
                    {"result": arr}, header=header, timeout_s=timeout_s)
                with slot:
                    sg = SGList()
                    sg.add_array(arr, slot.tree["result"])
                    get_engine().run_sg(sg, tag="reply_fill")
            else:
                self.transport.send(tree, header=header, mode="sync",
                                    timeout_s=timeout_s)
        except (TimeoutError, ChannelClosed):
            self.dead = True        # unresponsive or vanished: reap it
            raise
        finally:
            self.done()
            if t0 or c0 is not None:
                rid = header.get(_trace.RID_KEY, 0) if header else 0
                rid = rid if isinstance(rid, int) else 0
                if t0:
                    _trace.emit(_trace.REPLY_FILL, t0, rid=rid)
                if c0 is not None:
                    _hw.end(c0, "reserve_fill", rid=rid,
                            nbytes=arr.nbytes
                            if isinstance(arr, np.ndarray) else 0)


@dataclass
class ReactorStats:
    """Aggregate sweep counters (per-connection detail lives on Connection)."""
    sweeps: int = 0
    messages: int = 0
    idle_sleeps: int = 0
    throttled: int = 0         # sweeps that skipped a conn at max_inflight
    disconnects: int = 0
    errors: int = 0            # on_message raised (message dropped, loop lives)
    zero_copy_recvs: int = 0   # requests delivered as held leases (no copy)
    heap_reaped: int = 0       # leaked bulk-heap extents freed at reap time
    batched_drains: int = 0    # drain pulls that yielded >1 message at once
    stale_reaped: int = 0      # conns reaped on heartbeat staleness (crash)
    orphan_reaped: int = 0     # never-attached handshake orphans reclaimed


class Reactor:
    """Round-robin poller over many transports in a single thread.

    ``on_message(conn, lease)`` receives a
    :class:`~repro.ipc.channel.RecvLease`: ``lease.tree``/``lease.header``
    carry the request, and when ``lease.held`` the views point into the
    client's ring slot — the consumer must ``release()`` it once the
    payload is consumed (the fabric does this after batch gather).

    ``on_messages(conn, leases)``, when given, takes precedence: each
    drain pull hands over *every* message it got in one call — a client's
    coalesced frame (K sub-messages behind one ring poll, see
    :meth:`~repro.ipc.channel.DataChannel.try_recv_many`) flows into
    batch formation as one list instead of K separate callback+poll
    iterations.
    """

    def __init__(self, policy: Optional[OffloadPolicy] = None,
                 on_message: Optional[Callable[[Connection, RecvLease],
                                               None]] = None,
                 on_disconnect: Optional[Callable[[Connection], None]] = None,
                 max_drain_per_sweep: int = 8,
                 max_inflight: int = 16,
                 zero_copy: Optional[bool] = None,
                 on_messages: Optional[Callable[[Connection,
                                                 list], None]] = None):
        self.policy = policy or OffloadPolicy()
        self.on_message = on_message
        self.on_messages = on_messages
        self.on_disconnect = on_disconnect
        self.max_drain_per_sweep = max_drain_per_sweep
        self.max_inflight = max_inflight
        self.zero_copy = (self.policy.zero_copy_serving if zero_copy is None
                          else zero_copy)
        self.stats = ReactorStats()
        self._conns: dict[int, Connection] = {}
        self._lock = threading.Lock()
        self._next_cid = 0
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None

    # -- registry -------------------------------------------------------------
    def add(self, transport: ShmTransport) -> Connection:
        """Register a transport; it is polled from the next sweep on."""
        with self._lock:
            conn = Connection(self._next_cid, transport)
            self._conns[conn.cid] = conn
            self._next_cid += 1
        return conn

    def connections(self) -> list[Connection]:
        """Snapshot of live connections (stable order by client id)."""
        with self._lock:
            return [self._conns[k] for k in sorted(self._conns)]

    def __len__(self) -> int:
        return len(self._conns)

    def _reap(self, conn: Connection) -> None:
        with self._lock:
            self._conns.pop(conn.cid, None)
        self.stats.disconnects += 1
        if self.on_disconnect is not None:
            self.on_disconnect(conn)
        try:
            # crash-reap leaked heap extents (a client killed mid-send or
            # holding reply leases) before teardown so the leak is counted;
            # force=True: reaped connections are dead by definition (their
            # flag is up or their reply path already failed)
            self.stats.heap_reaped += conn.transport.reap_heap(force=True)
        except Exception:
            pass
        conn.transport.close()          # creator side: unlinks the arena

    # -- the sweep ------------------------------------------------------------
    def _drain(self, conn: Connection) -> int:
        """Pull up to the fairness quantum from one connection's rx ring,
        in batched sweeps: one ``try_recv_many`` drains a whole coalesced
        frame (or several queued small messages) per poll iteration."""
        drained = 0
        while drained < self.max_drain_per_sweep and not conn.dead:
            budget = min(self.max_drain_per_sweep - drained,
                         self.max_inflight - conn.inflight)
            if budget <= 0:
                self.stats.throttled += 1
                return drained          # admission cap: leave rest in its ring
            t0 = _trace.now() if _trace.TRACE.enabled else 0
            c0 = _hw.begin() if _hw.PROF.enabled else None
            try:
                items = conn.transport.data.try_recv_many(
                    budget, copy=not self.zero_copy)
            except ChannelClosed:
                items = []
            if not items:
                break
            if t0:
                _trace.emit(_trace.REACTOR_DRAIN, t0, arg=len(items))
            if c0 is not None:
                # non-empty pulls only: metering every empty spin poll
                # would cost 2 syscalls per sweep and swamp the profile
                _hw.end(c0, "ring_poll", nbytes=_lease_bytes(items))
            if len(items) > 1:
                self.stats.batched_drains += 1
            drained += len(items)
            leases = []
            for item in items:
                if isinstance(item, RecvLease):
                    leases.append(item)
                    self.stats.zero_copy_recvs += 1
                else:                   # copy-out mode: already released
                    leases.append(RecvLease(item[0], item[1], None))
                conn.begin()
            # lane tracking: remember the most urgent priority class this
            # drain saw, so the next sweep visits this client in lane order
            prios = [p for p in ((lease.header or {}).get(PRIO_KEY, 0)
                                 for lease in leases) if isinstance(p, int)]
            if prios:
                conn.lane = min(prios)
            if self.on_messages is not None:
                try:
                    self.on_messages(conn, leases)
                except Exception:
                    # a failing batch handoff must not kill the sweep
                    # thread (which serves every client); drop the batch,
                    # settle accounting
                    for lease in leases:
                        lease.release()
                        conn.done()
                    self.stats.errors += 1
            elif self.on_message is not None:
                for lease in leases:
                    try:
                        self.on_message(conn, lease)
                    except Exception:
                        # one malformed message must not kill the sweep
                        # thread; drop it, settle accounting
                        lease.release()
                        conn.done()
                        self.stats.errors += 1
            else:
                for lease in leases:
                    lease.release()
        return drained

    def poll_once(self) -> int:
        """One fair sweep over every connection, in lane order (each
        client's last-seen priority class, then client id — a lane-0
        client is drained before best-effort lanes within every sweep,
        while the per-connection quantum still bounds any one client's
        share); returns messages drained."""
        self.stats.sweeps += 1
        total = 0
        for conn in sorted(self.connections(),
                           key=lambda c: (c.lane, c.cid)):
            tr = conn.transport
            tr.heartbeat()              # server liveness stamp (rate-limited)
            n = self._drain(conn)
            total += n
            # reap only after an *empty* drain: a closing peer's in-flight
            # messages are still delivered before the connection is torn
            # down.  A dead connection (reply path failed) is reaped
            # unconditionally — late callbacks hitting its closed transport
            # are swallowed by the dispatcher's completion containment.
            # Two liveness verdicts join the closed flag: a *crashed*
            # heartbeating client (stamps stopped: stale) and a handshake
            # orphan (registered but never attached/stamped/sent within the
            # connect deadline) — both leak arenas/extents if left alone.
            if not (conn.dead or (n == 0 and conn.inflight == 0)):
                continue
            stale = orphan = False
            if not conn.dead and not tr.peer_closed:
                if tr.peer_heartbeat_stamped:
                    stale = tr.peer_stale()
                else:
                    orphan = (conn.received == 0
                              and tr.peer_heartbeat_age_s()
                              > tr.policy.retry.connect_timeout_s)
            if conn.dead or tr.peer_closed or stale or orphan:
                if stale:
                    self.stats.stale_reaped += 1
                if orphan:
                    self.stats.orphan_reaped += 1
                self._reap(conn)
        self.stats.messages += total
        return total

    def _loop(self) -> None:
        quantum = self.policy.poll_interval_us * 1e-6
        spin_s = self.policy.spin_us * 1e-6
        spin_deadline = time.perf_counter() + spin_s
        # traced, the thread's CPU (spins and quantum sleeps included) is
        # emitted per stretch of the loop, not per sweep
        meter = _hw.LoopMeter(_trace.REACTOR_LOOP)
        while not self._stop.is_set():
            if _trace.TRACE.enabled:
                meter.tick()
            if self.poll_once() > 0:
                spin_deadline = time.perf_counter() + spin_s
                continue
            if time.perf_counter() < spin_deadline:
                time.sleep(0)           # spin phase: catch streamers fast
            else:
                self.stats.idle_sleeps += 1
                time.sleep(quantum)     # quantum phase: stay CPU-polite
        meter.flush()

    # -- lifecycle ------------------------------------------------------------
    def start(self) -> "Reactor":
        """Run the sweep loop in a daemon thread."""
        self._thread = threading.Thread(target=self._loop, daemon=True,
                                        name="rocket-reactor")
        self._thread.start()
        return self

    def close(self) -> None:
        """Stop the loop and close every registered transport."""
        self._stop.set()
        if self._thread is not None:
            self._thread.join(timeout=self.policy.retry.join_timeout_s)
            self._thread = None
        with self._lock:
            conns, self._conns = list(self._conns.values()), {}
        for conn in conns:
            conn.transport.close()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()
