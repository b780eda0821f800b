"""Fixed-slot SPSC rings over a shared-memory arena (queue pairs, §IV-C).

One :class:`Ring` is a single-producer/single-consumer ring of ``n_slots``
fixed-size slots living inside a :class:`~repro.ipc.shm.SharedMemoryArena`.
Each slot is::

    [ slot header (64 B) | meta region (meta_bytes) | payload (slot_bytes) ]

with the header holding the slot *state flag* — the paper's completion flag —
plus the published payload/meta lengths and a monotonically increasing
message sequence number.  The producer cycles tail→slots, the consumer
head→slots; the state flag is the only synchronization point:

    EMPTY --producer--> WRITING --publish--> READY --consumer--> READING
      ^                                                              |
      +-------------------------- release --------------------------+

The ring's region starts with a 64 B header of two doorbells
(:mod:`repro.ipc.doorbell`), one per thing a side can wait for::

    [ ready bell | ready sleeper | empty bell | empty sleeper | ... ]  uint32

The producer bumps the *ready* bell on every publish and the consumer
sleeps on it; the consumer bumps the *empty* bell on every release and a
producer blocked on a full ring sleeps on it.  Each word has one writing
side, so a plain store is the bump.

Completion waits use the repo's hybrid polling (``core.latency`` +
``core.policy``): optional size-aware deferral (sleep most of the predicted
copy latency), then yield-only spins for ``spin_us``, then a futex sleep on
the doorbell — the UMWAIT analogue, woken by the publishing store — in
bounded slices.  Where futex(2) is missing, short passive waits of
``poll_interval_us`` replace the sleep.  Pre-mapping is inherited from the
arena: all slots are first-touched at creation, so steady state never
faults.
"""
from __future__ import annotations

import math
import time
from dataclasses import dataclass
from typing import Optional

import numpy as np

from repro.core.latency import LatencyModel
from repro.core.policy import OffloadPolicy
from repro.ft import inject as _inject
from repro.ipc import doorbell as _doorbell
from repro.ipc.shm import SharedMemoryArena
from repro.obs import trace as _trace

SLOT_HEADER_BYTES = 64
RING_HEADER_BYTES = 64
_ALIGN = 64

# ring header words (uint32): each doorbell, its sleeper mark right after
_BELL_READY, _BELL_EMPTY = 0, 2
# longest single futex sleep: the net under a lost wake-up, and how soon
# a peer flag raised without a doorbell (a crash never rings) is seen
_SLICE_S = 0.5

# slot states (int64 stores — single aligned word, untorn)
EMPTY, WRITING, READY, READING = 0, 1, 2, 3

# message-kind flags (slot header word 4, published with the state flip):
# FLAG_HEAP marks a large message whose payload lives in bulk-heap extents
# (ipc/heap.py); the slot carries only the compact extent descriptor.
# FLAG_COALESCED marks a microbatch frame: the slot carries K independent
# sub-messages (sub-message table in the meta region, payloads packed
# back-to-back) published under ONE state flip — the small-message fast
# path that amortizes slot claim, meta encode, and doorbell K-ways.
# FLAG_CRC marks a slot whose header word 5 carries a CRC32 over the
# published meta bytes (OffloadPolicy.meta_checksum): the receiver
# verifies before decoding and quarantines mismatches as counted
# ``corrupt_drops`` instead of crashing the drain loop.
FLAG_HEAP = 1
FLAG_COALESCED = 2
FLAG_CRC = 4


class ChannelClosed(EOFError):
    """The peer endpoint shut down while we were waiting on the ring."""


def _align(n: int, a: int = _ALIGN) -> int:
    return (n + a - 1) // a * a


@dataclass(frozen=True)
class RingSpec:
    """Geometry of one ring; both endpoints must construct from the same
    spec (the transport embeds it in the arena descriptor)."""
    n_slots: int
    slot_bytes: int            # payload capacity per slot
    meta_bytes: int = 1024     # per-slot metadata capacity (pickled headers)

    @property
    def slot_stride(self) -> int:
        """Bytes from one slot's header to the next (64B-aligned regions)."""
        return SLOT_HEADER_BYTES + _align(self.meta_bytes) + \
            _align(self.slot_bytes)

    @property
    def region_bytes(self) -> int:
        """Total arena bytes this ring occupies (doorbell header + slots)."""
        return RING_HEADER_BYTES + self.n_slots * self.slot_stride


@dataclass
class RingStats:
    """Per-endpoint ring counters (local; shared counts live in the arena)."""
    produced: int = 0
    consumed: int = 0
    polls: int = 0               # spin-phase polls (nap-fallback ones too)
    full_waits: int = 0          # producer found ring full (backpressure)
    doorbell_sleeps: int = 0     # futex sleeps a waiter entered
    doorbell_wakes: int = 0      # futex wakes this end issued to a sleeper
    deferred_sleep_s: float = 0.0
    blocked_wait_s: float = 0.0


class _Slot:
    """Typed views over one slot's header/meta/payload regions."""

    def __init__(self, arena: SharedMemoryArena, offset: int, spec: RingSpec):
        self.hdr = arena.ndarray(offset, (8,), np.int64)   # state, seq, pay, meta, flags
        meta_off = offset + SLOT_HEADER_BYTES
        self.meta_view = arena.view(meta_off, spec.meta_bytes)
        pay_off = meta_off + _align(spec.meta_bytes)
        self.payload_view = arena.view(pay_off, spec.slot_bytes)

    # header word accessors (index names double as layout docs)
    @property
    def state(self) -> int:
        return int(self.hdr[0])

    @state.setter
    def state(self, v: int) -> None:
        self.hdr[0] = v

    @property
    def seq(self) -> int:
        return int(self.hdr[1])

    @seq.setter
    def seq(self, v: int) -> None:
        self.hdr[1] = v

    @property
    def payload_nbytes(self) -> int:
        return int(self.hdr[2])

    @payload_nbytes.setter
    def payload_nbytes(self, v: int) -> None:
        self.hdr[2] = v

    @property
    def meta_nbytes(self) -> int:
        return int(self.hdr[3])

    @meta_nbytes.setter
    def meta_nbytes(self, v: int) -> None:
        self.hdr[3] = v

    @property
    def flags(self) -> int:
        return int(self.hdr[4])

    @flags.setter
    def flags(self, v: int) -> None:
        self.hdr[4] = v

    def drop_views(self) -> None:
        """Release buffer exports so the arena can close."""
        self.hdr = None
        self.meta_view = None
        self.payload_view = None


class SlotWriter:
    """Producer-side lease on a WRITING slot; ``publish`` flips it READY.

    This is the ring's **reserve-then-fill** primitive: ``Ring.acquire``
    reserves the slot, the caller fills ``payload``/``meta`` in place
    (e.g. packing a reply straight into the destination slot with no
    staging copy), and ``publish`` is the doorbell.  ``abort`` releases a
    reserved slot that cannot be filled: it publishes a zero-meta
    sentinel the data-channel receive path silently skips, so the SPSC
    cursor chain stays intact (a plain state rollback would strand the
    consumer, which waits on slots strictly in order)."""

    def __init__(self, ring: "Ring", slot: _Slot, seq: int):
        self._ring = ring
        self.slot = slot
        self.seq = seq

    @property
    def payload(self) -> memoryview:
        """Writable view over the slot's full payload region."""
        return self.slot.payload_view

    @property
    def meta(self) -> memoryview:
        """Writable view over the slot's metadata region."""
        return self.slot.meta_view

    def publish(self, payload_nbytes: int, meta_nbytes: int = 0,
                flags: int = 0, meta_crc: int = -1) -> None:
        """Flip the slot READY — the paper's completion-flag store.

        ``flags`` is the message-kind word (:data:`FLAG_HEAP`: the payload
        lives in bulk-heap extents named by the meta, ``payload_nbytes``
        then counts *heap* bytes and the slot payload region is unused).
        Always stored, so slot reuse cannot leak a stale flag.

        ``meta_crc >= 0`` stores a CRC32 of the meta bytes in header
        word 5 and raises :data:`FLAG_CRC`, published atomically with the
        state flip (the checksum rides the same doorbell it guards)."""
        s = self.slot
        if _inject._PLANE is not None and meta_nbytes > 0:
            if _inject.fire("ring.publish.drop") is not None:
                # the message vanishes in flight: publish the zero-meta
                # skip sentinel so the SPSC cursor chain stays intact
                payload_nbytes = meta_nbytes = flags = 0
                meta_crc = -1
            else:
                torn = _inject.fire("ring.publish.torn")
                if torn is not None:
                    s.meta_view[0] ^= (torn.arg or 0xFF) & 0xFF
        if meta_crc >= 0:
            s.hdr[5] = meta_crc
            flags |= FLAG_CRC
        s.payload_nbytes = payload_nbytes
        s.meta_nbytes = meta_nbytes
        s.flags = flags
        s.seq = self.seq
        s.state = READY            # the publishing store (completion flag)
        self._ring.ring_doorbell(READY)
        self._ring._produced[0] += 1
        self._ring.stats.produced += 1

    def abort(self) -> None:
        """Give the reserved slot back as a skip sentinel (zero meta)."""
        self.publish(0, 0, 0)


class SlotReader:
    """Consumer-side lease on a READING slot; ``release`` frees it."""

    def __init__(self, ring: "Ring", slot: _Slot):
        self._ring = ring
        self.slot = slot
        self.seq = slot.seq
        self.payload_nbytes = slot.payload_nbytes
        self.meta_nbytes = slot.meta_nbytes
        self.flags = slot.flags
        # published meta checksum (valid only when flags & FLAG_CRC)
        self.meta_crc = int(slot.hdr[5]) if (self.flags & FLAG_CRC) else -1

    @property
    def payload(self) -> memoryview:
        """Read-only view of the published payload bytes (zero-copy)."""
        return self.slot.payload_view[:self.payload_nbytes]

    @property
    def meta(self) -> bytes:
        """The published metadata bytes (copied out; they are small)."""
        return bytes(self.slot.meta_view[:self.meta_nbytes])

    def payload_array(self, offset: int, shape, dtype,
                      copy: bool = True) -> np.ndarray:
        """Typed view (or copy) of a sub-range of the payload."""
        dtype = np.dtype(dtype)
        count = math.prod(shape)
        arr = np.frombuffer(self.slot.payload_view, dtype, count=count,
                            offset=offset).reshape(shape)
        return arr.copy() if copy else arr

    def release(self) -> None:
        """Recycle the slot (EMPTY): any payload views become invalid.

        Safe after transport teardown: if the endpoint was closed while
        this lease was still held (a reaped connection whose requests were
        queued in the dispatcher), the slot views are already dropped and
        there is nothing to recycle — releasing is a no-op rather than a
        crash in whoever held the lease."""
        try:
            self.slot.state = EMPTY
            self._ring.ring_doorbell(EMPTY)
            self._ring._consumed[0] += 1
        except TypeError:              # drop_views() ran: slot/counters gone
            return
        self._ring.stats.consumed += 1

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.release()


class Ring:
    """One directional ring endpoint (construct with the producer or
    consumer role; both map the same arena region)."""

    def __init__(self, arena: SharedMemoryArena, offset: int, spec: RingSpec,
                 policy: Optional[OffloadPolicy] = None,
                 latency: Optional[LatencyModel] = None,
                 counter_words: tuple[int, int] = (4, 5)):
        self.arena = arena
        self.spec = spec
        self.policy = policy or OffloadPolicy()
        self.latency = latency or LatencyModel()
        self.stats = RingStats()
        self._bells = arena.ndarray(offset, (4,), np.uint32)
        # futex addresses from the numpy view: no second buffer export
        base = self._bells.ctypes.data
        self._bell_addr = {READY: base + 4 * _BELL_READY,
                           EMPTY: base + 4 * _BELL_EMPTY}
        self._slots = [
            _Slot(arena, offset + RING_HEADER_BYTES + i * spec.slot_stride,
                  spec)
            for i in range(spec.n_slots)
        ]
        # shared produced/consumed counters (introspection + wraparound tests)
        words = arena.control_words()
        self._produced = words[counter_words[0]:counter_words[0] + 1]
        self._consumed = words[counter_words[1]:counter_words[1] + 1]
        self._head = 0             # consumer cursor (local: SPSC)
        self._tail = 0             # producer cursor (local: SPSC)
        self._seq = 0
        self._closed_word: Optional[np.ndarray] = None

    def bind_shutdown_word(self, word: np.ndarray) -> None:
        """A shared flag checked inside waits: nonzero → peer is gone."""
        self._closed_word = word

    def _peer_closed(self) -> bool:
        return self._closed_word is not None and int(self._closed_word[0]) != 0

    @property
    def peer_closed(self) -> bool:
        """True once the bound shutdown word says the peer endpoint is gone
        (public so channel layers can surface :class:`ChannelClosed`
        consistently instead of poking ring internals)."""
        return self._peer_closed()

    @property
    def produced(self) -> int:
        """Messages published into this ring (shared counter)."""
        return int(self._produced[0])

    @property
    def consumed(self) -> int:
        """Messages released from this ring (shared counter)."""
        return int(self._consumed[0])

    # -- hybrid polling core --------------------------------------------------
    def _wait_state(self, slot: _Slot, want: int, timeout_s: float,
                    hint_nbytes: int = 0) -> bool:
        """Wait for ``slot.state == want`` with deferral + short waits."""
        if slot.state == want:
            return True
        if _trace.TRACE.enabled:           # slow path only: fast path above
            tt0 = _trace.now()
            ok = self._wait_state_slow(slot, want, timeout_s, hint_nbytes)
            _trace.emit(_trace.RING_WAIT, tt0, arg=hint_nbytes)
            return ok
        return self._wait_state_slow(slot, want, timeout_s, hint_nbytes)

    def _wait_state_slow(self, slot: _Slot, want: int, timeout_s: float,
                         hint_nbytes: int) -> bool:
        """Deferral + spin + doorbell body of :meth:`_wait_state`."""
        t0 = time.perf_counter()
        if hint_nbytes > 0:
            # size-aware deferral: sleep most of the predicted copy latency
            defer = self.latency.defer_seconds(hint_nbytes,
                                               self.policy.defer_fraction)
            if defer > 0:
                time.sleep(min(defer, timeout_s))
                self.stats.deferred_sleep_s += min(defer, timeout_s)
            if slot.state == want:
                return True
        # spin phase: yield-only polls so a streaming peer is caught at
        # memcpy latency even where sleep() granularity is ~1ms
        spin_deadline = time.perf_counter() + self.policy.spin_us * 1e-6
        while time.perf_counter() < spin_deadline:
            self.stats.polls += 1
            if slot.state == want:
                self.stats.blocked_wait_s += time.perf_counter() - t0
                return True
            time.sleep(0)
        deadline = t0 + timeout_s
        if _doorbell.AVAILABLE:
            ok = self._doorbell_wait(slot, want, deadline)
        else:
            ok = self._nap_wait(slot, want, deadline)
        self.stats.blocked_wait_s += time.perf_counter() - t0
        return ok

    def _doorbell_wait(self, slot: _Slot, want: int,
                       deadline: float) -> bool:
        """Sleep in the kernel on ``want``'s doorbell until the slot turns.

        No wake-up is lost.  The waiter marks itself, reads the bell, then
        checks the slot and the peer's closed flag, and sleeps only while
        the bell still reads what it read.  The publisher stores the state
        (or the closed flag), bumps the bell, fences, then reads the mark.
        Either the publisher sees the mark and wakes the sleeper, or its
        stores were visible before the waiter's mark was: then the
        kernel's compare, which it orders after the mark with a full
        barrier, sees the bumped bell and returns at once.  A bump that
        lands between the read and the sleep is the compare's case too.
        The bounded slice is only the net under that argument."""
        words = self._bells
        bell = _BELL_READY if want == READY else _BELL_EMPTY
        mark = bell + 1
        addr = self._bell_addr[want]
        words[mark] = 1
        try:
            while True:
                seen = int(words[bell])
                if slot.state == want:
                    return True
                if self._peer_closed():
                    raise ChannelClosed("peer endpoint closed the transport")
                left = deadline - time.perf_counter()
                if left <= 0:
                    return False
                self.stats.doorbell_sleeps += 1
                _doorbell.wait(addr, seen, min(left, _SLICE_S))
        finally:
            words[mark] = 0

    def _nap_wait(self, slot: _Slot, want: int, deadline: float) -> bool:
        """Fallback without futex(2): passive ``poll_interval_us`` naps."""
        quantum = self.policy.poll_interval_us * 1e-6
        while slot.state != want:
            self.stats.polls += 1
            if self._peer_closed():
                raise ChannelClosed("peer endpoint closed the transport")
            if time.perf_counter() > deadline:
                return False
            time.sleep(quantum)      # passive short wait (UMWAIT analogue)
        return True

    def ring_doorbell(self, state: int) -> None:
        """Bump the doorbell that waiters for ``state`` (READY or EMPTY)
        sleep on, and wake them if one is marked.  Called by the side
        that just stored ``state`` (or raised its closed flag); see
        :meth:`_doorbell_wait` for the ordering."""
        if not _doorbell.AVAILABLE:
            return
        words = self._bells
        bell = _BELL_READY if state == READY else _BELL_EMPTY
        words[bell] = (int(words[bell]) + 1) & 0xFFFFFFFF
        _doorbell.fence()
        if words[bell + 1]:
            _doorbell.wake(self._bell_addr[state])
            self.stats.doorbell_wakes += 1

    # -- producer side --------------------------------------------------------
    def try_acquire(self) -> Optional[SlotWriter]:
        """Claim the next slot without blocking; None while the ring is full."""
        slot = self._slots[self._tail % self.spec.n_slots]
        if slot.state != EMPTY:
            return None
        slot.state = WRITING
        self._tail += 1
        self._seq += 1
        return SlotWriter(self, slot, self._seq)

    def acquire(self, timeout_s: float = 30.0) -> SlotWriter:
        """Claim the next slot, blocking while the ring is full
        (backpressure = the paper's bounded queue-pair depth)."""
        slot = self._slots[self._tail % self.spec.n_slots]
        if slot.state != EMPTY:
            self.stats.full_waits += 1
            if not self._wait_state(slot, EMPTY, timeout_s):
                raise TimeoutError(
                    f"ring full for {timeout_s}s (consumer stalled?)")
        slot.state = WRITING
        self._tail += 1
        self._seq += 1
        return SlotWriter(self, slot, self._seq)

    # -- consumer side --------------------------------------------------------
    def try_poll(self) -> Optional[SlotReader]:
        """Take the next READY slot without blocking; None when empty."""
        if _inject._PLANE is not None:
            _inject.stall("ring.poll.stall")
        slot = self._slots[self._head % self.spec.n_slots]
        if slot.state != READY:
            return None
        slot.state = READING
        self._head += 1
        return SlotReader(self, slot)

    def wait_recv(self, timeout_s: float = 30.0,
                  hint_nbytes: int = 0) -> SlotReader:
        """Block (hybrid polling) until a message is READY and lease it."""
        if _inject._PLANE is not None:
            _inject.stall("ring.poll.stall")
        slot = self._slots[self._head % self.spec.n_slots]
        if not self._wait_state(slot, READY, timeout_s, hint_nbytes):
            raise TimeoutError(f"no message within {timeout_s}s")
        slot.state = READING
        self._head += 1
        return SlotReader(self, slot)

    def drop_views(self) -> None:
        """Release every buffer export so the arena can be closed."""
        for s in self._slots:
            s.drop_views()
        self._bells = None
        self._produced = None
        self._consumed = None
        self._closed_word = None
