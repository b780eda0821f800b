"""Cross-process shm transport: one arena, four rings, two typed channels.

:class:`ShmTransport` packages a full connection between exactly two
processes (the paper's client↔server queue-pair setup):

- a **data channel** per direction (numpy pytrees; slots for the common
  case, per-connection bulk-heap extents for large payloads);
- a **control channel** per direction (small slots, pickled commands);
- a **bulk-heap segment** (``<name>.h``, :mod:`repro.ipc.heap`) minted by
  the creator when ``spec.heap_extents > 0`` — the large-message
  datapath's extent arena, torn down/unlinked with the transport and
  crash-reaped (:meth:`ShmTransport.reap_heap`) when a peer dies holding
  extents;
- a geometry descriptor at the head of the arena, written by the creator
  under a seqlock and read by the attacher — so the attaching process only
  needs the *name* (connection setup = one validated attach, after which
  everything is pre-mapped and fault-free);
- per-endpoint shutdown flags (control words) that turn blocked ring waits
  into :class:`~repro.ipc.ring.ChannelClosed` instead of deadlocks.

Arena control-word map::

    0  descriptor seqlock        1 creator-closed     2 attacher-closed
    3  descriptor-ready flag
    4/5   c2s data produced/consumed        6/7   s2c data produced/consumed
    8/9   c2s ctrl produced/consumed        10/11 s2c ctrl produced/consumed
    12 creator heartbeat stamp   13 attacher heartbeat stamp

Heartbeat words carry ``time.perf_counter_ns()`` stamps (CLOCK_MONOTONIC
on Linux — one timebase for every process on the host, the same one the
tracer and deadlines use).  Each side stamps only its own word (server on
reactor sweep, client on send), so the store is the usual single-writer
aligned int64; staleness thresholds live on ``OffloadPolicy.retry``.  A
peer that *crashes* (never raises its closed flag) is detected by
:meth:`ShmTransport.peer_stale` going true — the trigger for client
reconnect and server-side connection reap.
"""
from __future__ import annotations

import os
import pickle
import struct
import time
from dataclasses import dataclass
from typing import Optional

from repro.core.latency import LatencyModel
from repro.core.policy import OffloadPolicy
from repro.ipc.channel import ControlChannel, DataChannel
from repro.ipc.heap import BulkHeap, HeapSpec
from repro.ipc.ring import EMPTY, READY, Ring, RingSpec, _align
from repro.ipc.shm import SharedMemoryArena, attach_retry

_DESCR_BYTES = 4096
_W_DESCR_LOCK, _W_CREATOR_CLOSED, _W_ATTACHER_CLOSED, _W_READY = 0, 1, 2, 3
_RING_WORDS = {"c2s_data": (4, 5), "s2c_data": (6, 7),
               "c2s_ctrl": (8, 9), "s2c_ctrl": (10, 11)}
_W_HB_CREATOR, _W_HB_ATTACHER = 12, 13


@dataclass(frozen=True)
class TransportSpec:
    """Geometry of one connection: slot counts/sizes for both ring kinds
    plus the bulk-heap extents (embedded in the arena descriptor so only
    the creator chooses it).

    Slots are deliberately small now that large payloads ride the heap:
    the slot arena only has to fit descriptors and sub-threshold messages,
    so per-client footprint is ``footprint_bytes`` instead of the old
    256 MB of fully-reserved 32 MB slots.  ``heap_extents=0`` disables the
    heap (pre-heap behaviour: slot capacity caps the message size).
    """
    data_slots: int = 4
    data_slot_bytes: int = 2 << 20
    data_meta_bytes: int = 4096
    ctrl_slots: int = 8
    ctrl_slot_bytes: int = 64 << 10
    heap_extent_bytes: int = 1 << 20      # bulk-heap base extent (pow2)
    heap_extents: int = 32                # per direction; 0 disables

    @property
    def data_ring(self) -> RingSpec:
        """Ring geometry for the two data directions."""
        return RingSpec(self.data_slots, self.data_slot_bytes,
                        self.data_meta_bytes)

    @property
    def ctrl_ring(self) -> RingSpec:
        """Ring geometry for the two control directions."""
        return RingSpec(self.ctrl_slots, self.ctrl_slot_bytes, 64)

    @property
    def heap(self) -> HeapSpec:
        """Bulk-heap geometry (``enabled`` False when heap_extents=0)."""
        return HeapSpec(self.heap_extent_bytes, self.heap_extents)

    def layout(self) -> dict:
        """Ring name → arena user-region offset (descriptor block first)."""
        off = _align(_DESCR_BYTES)
        out = {}
        for name, spec in (("c2s_data", self.data_ring),
                           ("s2c_data", self.data_ring),
                           ("c2s_ctrl", self.ctrl_ring),
                           ("s2c_ctrl", self.ctrl_ring)):
            out[name] = off
            off = _align(off + spec.region_bytes)
        out["__total__"] = off
        return out

    @property
    def footprint_bytes(self) -> int:
        """Total shared memory one connection maps (ring arena + heap
        segment) — the per-client cost a listener multiplies by
        ``max_clients`` (see docs/ARCHITECTURE.md for the formula)."""
        total = self.layout()["__total__"]
        if self.heap.enabled:
            total += self.heap.layout()["__total__"]
        return total


def _unique_name(prefix: str = "rocket") -> str:
    return f"{prefix}-{os.getpid()}-{time.monotonic_ns() & 0xFFFFFF:x}"


class ShmTransport:
    """One endpoint of a two-process shared-memory connection."""

    def __init__(self, arena: SharedMemoryArena, spec: TransportSpec,
                 side: str, policy: Optional[OffloadPolicy] = None,
                 latency: Optional[LatencyModel] = None,
                 heap: Optional[BulkHeap] = None):
        assert side in ("creator", "attacher")
        self.arena = arena
        self.spec = spec
        self.side = side
        self.policy = policy or OffloadPolicy()
        self.latency = latency or LatencyModel()
        self.heap = heap
        self._closed = False

        layout = spec.layout()
        words = arena.control_words()
        # my tx is c2s when I created the arena ("server" side of the name)
        tx_dir, rx_dir = (("c2s", "s2c") if side == "creator"
                          else ("s2c", "c2s"))

        def ring(direction: str, kind: str) -> Ring:
            key = f"{direction}_{kind}"
            rspec = spec.data_ring if kind == "data" else spec.ctrl_ring
            r = Ring(arena, layout[key], rspec, self.policy, self.latency,
                     counter_words=_RING_WORDS[key])
            peer_word = (_W_ATTACHER_CLOSED if side == "creator"
                         else _W_CREATOR_CLOSED)
            r.bind_shutdown_word(words[peer_word:peer_word + 1])
            return r

        self._rings = {
            "tx_data": ring(tx_dir, "data"), "rx_data": ring(rx_dir, "data"),
            "tx_ctrl": ring(tx_dir, "ctrl"), "rx_ctrl": ring(rx_dir, "ctrl"),
        }
        self.data = DataChannel(self._rings["tx_data"],
                                self._rings["rx_data"],
                                self.policy, self.latency, heap=heap)
        self.ctrl = ControlChannel(self._rings["tx_ctrl"],
                                   self._rings["rx_ctrl"])
        mine = (_W_CREATOR_CLOSED if side == "creator"
                else _W_ATTACHER_CLOSED)
        self._my_closed_word = words[mine:mine + 1]
        # liveness stamps: each side writes only its own word
        mine_hb = _W_HB_CREATOR if side == "creator" else _W_HB_ATTACHER
        peer_hb = _W_HB_ATTACHER if side == "creator" else _W_HB_CREATOR
        self._my_hb_word = words[mine_hb:mine_hb + 1]
        self._peer_hb_word = words[peer_hb:peer_hb + 1]
        self._last_beat = 0.0
        self._born = time.perf_counter()

    # -- construction ---------------------------------------------------------
    @classmethod
    def create(cls, name: Optional[str] = None,
               spec: TransportSpec = TransportSpec(),
               policy: Optional[OffloadPolicy] = None,
               latency: Optional[LatencyModel] = None) -> "ShmTransport":
        """Allocate the arena, publish the geometry descriptor, raise READY."""
        name = name or _unique_name()
        layout = spec.layout()
        arena = SharedMemoryArena(name, size=layout["__total__"], create=True)
        # mint the bulk-heap segment BEFORE raising READY: the attacher
        # learns heap geometry from the descriptor and maps it immediately
        heap = (BulkHeap.create(f"{name}.h", spec.heap)
                if spec.heap.enabled else None)
        # publish geometry under the descriptor seqlock, then raise READY
        blob = pickle.dumps(spec, protocol=pickle.HIGHEST_PROTOCOL)
        if len(blob) + 4 > _DESCR_BYTES:
            raise ValueError("transport spec descriptor too large")
        lock = arena.seqlock(_W_DESCR_LOCK)
        with lock.write():
            view = arena.view(0, _DESCR_BYTES)
            struct.pack_into("<I", view, 0, len(blob))
            view[4:4 + len(blob)] = blob
        arena.control_words()[_W_READY] = 1
        return cls(arena, spec, "creator", policy, latency, heap=heap)

    @classmethod
    def attach(cls, name: str, policy: Optional[OffloadPolicy] = None,
               latency: Optional[LatencyModel] = None,
               timeout_s: float = 30.0) -> "ShmTransport":
        """Open a peer's arena by name, reading geometry from its descriptor."""
        arena = attach_retry(name, timeout_s)
        words = arena.control_words()
        deadline = time.perf_counter() + timeout_s
        while int(words[_W_READY]) == 0:       # creator still writing layout
            if time.perf_counter() > deadline:
                arena.close()
                raise TimeoutError(f"transport {name!r} never became ready")
            time.sleep(0.001)

        lock = arena.seqlock(_W_DESCR_LOCK)

        def read_spec():
            view = arena.view(0, _DESCR_BYTES)
            (n,) = struct.unpack_from("<I", view, 0)
            return bytes(view[4:4 + n])

        spec = pickle.loads(lock.read(read_spec))
        heap = (BulkHeap.attach(f"{name}.h", spec.heap, timeout_s)
                if spec.heap.enabled else None)
        return cls(arena, spec, "attacher", policy, latency, heap=heap)

    # -- convenience ----------------------------------------------------------
    @property
    def name(self) -> str:
        """Arena name — the address a peer attaches by."""
        return self.arena.name

    @property
    def peer_closed(self) -> bool:
        """True once the other endpoint announced shutdown (its closed
        flag is up); in-flight ring messages may still be drainable."""
        if self._closed:
            return True
        word = (_W_ATTACHER_CLOSED if self.side == "creator"
                else _W_CREATOR_CLOSED)
        return int(self.arena.control_words()[word]) != 0

    # -- liveness (heartbeat words 12/13) -------------------------------------
    def heartbeat(self, force: bool = False) -> None:
        """Stamp my liveness word, rate-limited to
        ``policy.retry.heartbeat_interval_s`` (one clock read per call in
        the common no-op case; the server calls this every reactor sweep,
        the client on every send)."""
        now = time.perf_counter()
        if not force and \
                now - self._last_beat < self.policy.retry.heartbeat_interval_s:
            return
        self._last_beat = now
        word = self._my_hb_word
        if word is not None:
            word[0] = time.perf_counter_ns()

    @property
    def peer_heartbeat_stamped(self) -> bool:
        """True once the peer has stamped its heartbeat word at least
        once.  Liveness-based reaping keys on this: a peer that never
        heartbeats (raw transports, older clients) is never stale-reaped —
        only a peer that *was* heartbeating and stopped is presumed
        crashed."""
        word = self._peer_hb_word
        return word is not None and int(word[0]) != 0

    def peer_heartbeat_age_s(self) -> float:
        """Seconds since the peer last stamped its heartbeat word; a peer
        that never stamped is as old as this endpoint (so a connection
        whose peer never showed up still goes stale)."""
        word = self._peer_hb_word
        if word is None:
            return float("inf")
        stamp = int(word[0])
        if stamp == 0:
            return time.perf_counter() - self._born
        return max(0.0, (time.perf_counter_ns() - stamp) / 1e9)

    def peer_stale(self, stale_s: Optional[float] = None) -> bool:
        """Liveness verdict: the peer announced shutdown, or its heartbeat
        is older than ``stale_s`` (default
        ``policy.retry.heartbeat_stale_s``).  This is what distinguishes a
        *crashed* peer (flag never raised) from a merely idle one — the
        trigger for client ``reconnect()`` and server-side reap."""
        if self.peer_closed:
            return True
        if stale_s is None:
            stale_s = self.policy.retry.heartbeat_stale_s
        return self.peer_heartbeat_age_s() > stale_s

    def send(self, tree, header: Optional[dict] = None, **kw):
        """Send a pytree on the data channel (mode semantics from policy)."""
        return self.data.send(tree, header, **kw)

    def recv(self, **kw):
        """Receive ``(tree, header)`` — or a RecvLease with ``copy=False``."""
        return self.data.recv(**kw)

    def send_msg(self, obj, **kw) -> None:
        """Send a small pickled command on the control channel."""
        self.ctrl.send_msg(obj, **kw)

    def recv_msg(self, **kw):
        """Blocking receive of one control message."""
        return self.ctrl.recv_msg(**kw)

    def stats(self) -> dict:
        """Channel-, ring-, heap-, and governor-level counters for this
        endpoint."""
        out = {
            "data": self.data.stats.snapshot(),
            "rings": {k: vars(r.stats) for k, r in self._rings.items()},
        }
        if self.heap is not None:
            out["heap"] = self.heap.stats.snapshot()
        if self.data.governor is not None:
            out["governor"] = self.data.governor.snapshot()
        return out

    def metrics(self) -> dict:
        """The same counters as :meth:`stats`, flattened to dot-keys via
        the unified :class:`~repro.obs.metrics.MetricsRegistry` shape
        (``"data.sends"``, ``"rings.tx_data.polls"``, ...) — one flat dict
        a dashboard or benchmark row can diff with
        :meth:`~repro.obs.metrics.MetricsRegistry.delta`."""
        from repro.obs.metrics import MetricsRegistry
        reg = MetricsRegistry()
        reg.register("", self.stats)    # empty prefix: keys start at "data."
        return reg.snapshot()

    # -- lifecycle ------------------------------------------------------------
    def announce_close(self) -> None:
        """Raise this endpoint's closed flag so the peer's blocked ring
        waits fail fast with ChannelClosed (no deadlock on shutdown), and
        ring the doorbells the peer may sleep on: READY on my tx rings
        (it consumes them), EMPTY on my rx rings (it produces into them)."""
        if self._my_closed_word is not None:
            self._my_closed_word[0] = 1
            for key, r in self._rings.items():
                r.ring_doorbell(READY if key.startswith("tx") else EMPTY)

    def reap_heap(self, force: bool = False) -> int:
        """Crash-reap leaked bulk-heap extents after the peer died: frees
        both the extents *we* allocated that the dead receiver will never
        release (our tx direction) and the dead sender's half-filled,
        never-published allocations (our rx direction — only safe because
        a dead peer publishes nothing more and our rx ring is drained by
        the caller).  Returns extents freed; refuses while the peer still
        looks alive unless ``force``."""
        if self.heap is None:
            return 0
        if not (force or self.peer_closed):
            raise RuntimeError("refusing to reap heap extents from a peer "
                               "that has not closed (pass force=True only "
                               "when its process is known dead)")
        return (self.heap.reap(self.heap.tx_dir)
                + self.heap.reap(self.heap.rx_dir))

    def close(self, unlink: Optional[bool] = None) -> None:
        """Announce shutdown, drop all views, unmap (creator also unlinks
        both the ring arena and the heap segment)."""
        if self._closed:
            return
        self._closed = True
        self.announce_close()
        self.data.close()
        self._my_closed_word = None
        self._my_hb_word = None
        self._peer_hb_word = None
        for r in self._rings.values():
            r.drop_views()
        try:
            self.arena.close()
        except BufferError:
            # a zero-copy lease somewhere still pins a slot view (e.g. a
            # request drained from a connection that died mid-batch); the
            # mapping drops when the lease holder releases or the process
            # exits — unlinking below is still safe (POSIX destroys the
            # segment at last unmap), so a stuck lease cannot leak shm
            pass
        do_unlink = unlink if unlink is not None else (self.side == "creator")
        if self.heap is not None:
            self.heap.close()          # same BufferError tolerance inside
            if do_unlink:
                self.heap.unlink()
        if do_unlink:
            self.arena.unlink()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()
