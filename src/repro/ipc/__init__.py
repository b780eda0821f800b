"""repro.ipc — real cross-process shared-memory IPC with ROCKET modes.

The paper's runtime, made an actual inter-process transport (see
``docs/ARCHITECTURE.md`` for the layer diagram and control-word maps):

- :mod:`repro.ipc.shm`       — pre-mapped shared-memory arenas, seqlocks,
  and the exclusive-creation cross-process mutex
- :mod:`repro.ipc.ring`      — fixed-slot SPSC rings (queue pairs, §IV-C)
- :mod:`repro.ipc.doorbell`  — futex doorbells on shared words: where a
  ring waiter sleeps once its spin window has passed
- :mod:`repro.ipc.channel`   — typed numpy-pytree channels, sync/async/
  pipelined send modes with hybrid-polling completion
- :mod:`repro.ipc.heap`      — per-connection bulk heap: extent allocator
  for the large-message datapath (descriptor-passing over shared memory)
- :mod:`repro.ipc.transport` — one arena + four rings (+ heap segment)
  = one connection
- :mod:`repro.ipc.listener`  — multi-client rendezvous: registration
  mailbox + accept loop minting per-client transports
- :mod:`repro.ipc.reactor`   — one server thread multiplexing N client
  transports with round-robin fairness and admission caps
- :mod:`repro.ipc.worker`    — producer processes, the point-to-point
  dispatcher bridge, and the multi-client :class:`ServingFabric`
  (cross-client request batching)
"""
from repro.ipc.shm import SeqLock, SharedMemoryArena, ShmMutex, attach_retry
from repro.ipc.ring import ChannelClosed, Ring, RingSpec, SlotReader, SlotWriter
from repro.ipc.channel import (
    DEADLINE_KEY,
    PRIO_KEY,
    ChannelStats,
    ControlChannel,
    DataChannel,
    RecvLease,
    SendHandle,
    TxSlot,
    tree_nbytes,
)
from repro.ipc.heap import BulkHeap, HeapExhausted, HeapSpec
from repro.ipc.transport import ShmTransport, TransportSpec
from repro.ipc.listener import Listener, connect
from repro.ipc.reactor import Connection, Reactor
from repro.ipc.worker import (
    DispatcherServer,
    ProducerHandle,
    RemoteDispatcherClient,
    ServingFabric,
    make_source_from_spec,
    start_producer,
)

__all__ = [
    "BulkHeap", "ChannelClosed", "ChannelStats", "Connection", "DEADLINE_KEY",
    "PRIO_KEY",
    "ControlChannel", "DataChannel", "DispatcherServer", "HeapExhausted",
    "HeapSpec", "Listener", "ProducerHandle",
    "Reactor", "RecvLease", "RemoteDispatcherClient", "Ring", "RingSpec",
    "SendHandle", "SeqLock", "ServingFabric", "SharedMemoryArena",
    "ShmMutex", "ShmTransport", "SlotReader", "SlotWriter", "TransportSpec",
    "TxSlot", "attach_retry", "connect", "make_source_from_spec",
    "start_producer", "tree_nbytes",
]
