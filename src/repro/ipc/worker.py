"""Process runners over the shm transport: batch producers + RPC bridge.

Two roles:

- :func:`start_producer` spawns a **producer process** that attaches to a
  transport by name and streams source batches through the data channel —
  the real-IPC version of the input pipeline's producer side.  The control
  channel carries ``seek`` / ``stop`` commands back to the producer
  (checkpoint-restore and shutdown), and the producer marks end-of-stream
  with an ``eof`` header.

- :class:`DispatcherServer` / :class:`RemoteDispatcherClient` bridge the
  in-process :class:`~repro.core.dispatcher.RequestDispatcher` across the
  transport, so clients in *other processes* issue
  ``request(op, data, mode)`` / ``query(job_id)`` exactly like the paper's
  Listing 1 — sync blocks for the result, async/pipelined return a job id
  completed through :class:`QueryHandler` (the receiver thread sleeps on
  the reply ring's doorbell, the querying thread on the job's event).

- :class:`ServingFabric` is the multi-client generalization: a listener
  accepts any number of clients, a reactor multiplexes their transports in
  one thread, and pipelined requests from *different processes* are packed
  into single dispatcher batches (cross-client batch formation), replies
  demultiplexed by completion callback.  Clients reach it with
  :meth:`RemoteDispatcherClient.connect`.

Producer entry points are module-level functions (spawn-safe).
"""
from __future__ import annotations

import importlib
import multiprocessing as mp
import os
import threading
import time
from collections import deque
from concurrent.futures import ThreadPoolExecutor
from dataclasses import dataclass
from typing import Optional

import numpy as np

from repro.core.dispatcher import (DeadlineExceeded, QueryHandler, Request,
                                   RequestDispatcher)
from repro.core.latency import LatencyModel
from repro.core.policy import ExecutionMode, OffloadPolicy
from repro.ft import inject as _inject
from repro.ft.monitor import SLOMonitor
from repro.ipc.channel import DEADLINE_KEY, DEDUP_KEY, PRIO_KEY
from repro.ipc.ring import ChannelClosed
from repro.ipc.transport import ShmTransport, TransportSpec
from repro.obs import hwcounters as _hw
from repro.obs import trace as _trace
from repro.obs.metrics import MetricsRegistry, SLOTracker


# ---------------------------------------------------------------------------
# source construction inside the producer process
# ---------------------------------------------------------------------------

def make_source_from_spec(spec: dict):
    """Build a batch source in the child from a picklable spec dict.

    kinds:
      ``synthetic_lm``  — repro.data.SyntheticLMSource(cfg, shape, seed, ...)
      ``factory``       — dotted ``module:function`` called with ``kwargs``
    """
    kind = spec.get("kind", "synthetic_lm")
    if kind == "synthetic_lm":
        from repro.data.pipeline import SyntheticLMSource
        return SyntheticLMSource(spec["cfg"], spec["shape"],
                                 seed=spec.get("seed", 0),
                                 batch_override=spec.get("batch_override"))
    if kind == "factory":
        mod_name, fn_name = spec["path"].split(":")
        fn = getattr(importlib.import_module(mod_name), fn_name)
        return fn(**spec.get("kwargs", {}))
    raise ValueError(f"unknown source kind {kind!r}")


def _producer_entry(name: str, source_spec: dict, policy: OffloadPolicy,
                    n_batches: Optional[int]) -> None:
    """Child main: attach, stream batches, honor seek/stop commands."""
    transport = ShmTransport.attach(name, policy=policy)
    source = make_source_from_spec(source_spec)
    state = {"it": iter(source), "gen": 0}

    def apply_seek(cmd: dict) -> None:
        # gen: seek generation, lets the consumer discard stale in-flight
        # batches published before the restore
        source.restore({"seed": cmd.get("seed", source.seed),
                        "step": cmd["step"]})
        state["it"] = iter(source)
        state["gen"] = cmd.get("gen", state["gen"] + 1)
        transport.data.flush()

    try:
        while True:
            sent = 0
            while n_batches is None or sent < n_batches:
                cmd = transport.ctrl.try_recv_msg()
                if cmd is not None:
                    if cmd.get("cmd") == "stop":
                        return
                    if cmd.get("cmd") == "seek":
                        apply_seek(cmd)
                        continue
                step = getattr(source, "step", sent)
                batch = next(state["it"])
                # mode semantics come from the policy: sync publishes
                # inline, async/pipelined overlap production with the copy
                transport.send(batch, header={"step": step,
                                              "gen": state["gen"]})
                sent += 1
            transport.data.flush()
            transport.send({}, header={"eof": True, "gen": state["gen"]},
                           mode="sync")
            # linger: a late stop makes the consumer's close racefree, and a
            # late seek (restore on a finished stream) restarts production
            deadline = time.perf_counter() + policy.retry.linger_timeout_s
            resumed = False
            while time.perf_counter() < deadline:
                cmd = transport.ctrl.try_recv_msg()
                if cmd is not None:
                    if cmd.get("cmd") == "stop":
                        return
                    if cmd.get("cmd") == "seek":
                        apply_seek(cmd)
                        resumed = True
                        break
                time.sleep(0.005)
            if not resumed:
                return
    except ChannelClosed:
        pass
    finally:
        transport.close()


@dataclass
class ProducerHandle:
    """Consumer-side handle on a spawned producer process."""
    transport: ShmTransport
    process: mp.process.BaseProcess
    gen: int = 0                 # current seek generation (0 = initial stream)

    def recv_batch(self, timeout_s: Optional[float] = None):
        """Next (batch, header); header["eof"] marks end of stream.
        Default timeout is ``policy.retry.query_timeout_s``."""
        if timeout_s is None:
            timeout_s = self.transport.policy.retry.query_timeout_s
        return self.transport.recv(timeout_s=timeout_s)

    def seek(self, step: int, seed: Optional[int] = None) -> int:
        """Reposition the producer; returns the new generation.  Batches
        already in flight carry the old generation — discard headers whose
        ``gen`` differs (stale data, possibly from a different seed)."""
        self.gen += 1
        msg = {"cmd": "seek", "step": step, "gen": self.gen}
        if seed is not None:
            msg["seed"] = seed
        self.transport.send_msg(msg)
        return self.gen

    def stop(self, timeout_s: Optional[float] = None) -> None:
        """Stop the producer (command, then closed-flag, then terminate).
        Default timeout is ``policy.retry.join_timeout_s``."""
        retry = self.transport.policy.retry
        if timeout_s is None:
            timeout_s = retry.join_timeout_s
        try:
            if self.process.is_alive():
                self.transport.send_msg(
                    {"cmd": "stop"}, timeout_s=retry.shutdown_send_timeout_s)
        except (TimeoutError, ChannelClosed, ValueError):
            pass
        # raise our closed flag first: a producer blocked on a full ring
        # sees ChannelClosed instead of waiting out its acquire timeout
        self.transport.announce_close()
        self.process.join(timeout=timeout_s)
        if self.process.is_alive():
            self.process.terminate()
            self.process.join(timeout=retry.join_timeout_s)
        self.transport.close()


def start_producer(source_spec: dict,
                   policy: Optional[OffloadPolicy] = None,
                   spec: TransportSpec = TransportSpec(),
                   n_batches: Optional[int] = None,
                   name: Optional[str] = None,
                   ctx: Optional[mp.context.BaseContext] = None
                   ) -> ProducerHandle:
    """Create a transport and spawn a producer process streaming into it."""
    policy = policy or OffloadPolicy()
    transport = ShmTransport.create(name, spec, policy)
    ctx = ctx or mp.get_context("spawn")
    proc = ctx.Process(target=_producer_entry,
                       args=(transport.name, source_spec, policy, n_batches),
                       daemon=True)
    proc.start()
    return ProducerHandle(transport, proc)


# ---------------------------------------------------------------------------
# cross-process dispatcher bridge (paper Listing 1 across a real boundary)
# ---------------------------------------------------------------------------

class DispatcherServer:
    """Serves a :class:`RequestDispatcher`'s handlers to a remote client."""

    def __init__(self, dispatcher: RequestDispatcher,
                 transport: ShmTransport, workers: int = 2):
        self.dispatcher = dispatcher
        self.transport = transport
        self._pool = ThreadPoolExecutor(max_workers=workers,
                                        thread_name_prefix="rocket-ipc-srv")
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None

    def _reply(self, job_id: int, result, error: Optional[str]) -> None:
        tree = {} if error is not None else {"result": np.asarray(result)}
        self.transport.send(tree, header={"job_id": job_id, "error": error},
                            mode="sync")

    def _handle(self, header: dict, tree) -> None:
        job_id, op = header["job_id"], header["op"]
        mode = ExecutionMode(header.get("mode", "sync"))
        try:
            # route through the dispatcher so batching/stats apply; sync here
            # is fine — concurrency comes from the server pool
            if mode == ExecutionMode.SYNC:
                result = self.dispatcher.request(op, tree["data"], mode="sync")
            else:
                jid = self.dispatcher.request(op, tree["data"], mode=mode)
                result = self.dispatcher.query(jid)
            self._reply(job_id, result, None)
        except Exception as e:                      # surfaced client-side
            self._reply(job_id, None, f"{type(e).__name__}: {e}")

    def _loop(self) -> None:
        poll_s = self.transport.policy.retry.recv_poll_s
        while not self._stop.is_set():
            try:
                tree, header = self.transport.recv(timeout_s=poll_s)
            except TimeoutError:
                continue
            except ChannelClosed:
                break
            if header.get("shutdown"):
                break
            self._pool.submit(self._handle, header, tree)

    def serve_forever(self) -> None:
        """Serve on the caller's thread until shutdown/close."""
        self._loop()

    def start(self) -> "DispatcherServer":
        """Serve from a background daemon thread."""
        self._thread = threading.Thread(target=self._loop, daemon=True,
                                        name="rocket-ipc-serve")
        self._thread.start()
        return self

    def close(self) -> None:
        """Stop the serve loop and drain the handler pool."""
        self._stop.set()
        if self._thread is not None:
            self._thread.join(
                timeout=self.transport.policy.retry.join_timeout_s)
        self._pool.shutdown(wait=True)


class ServingFabric:
    """Multi-client serving: listener + reactor shards + shared dispatcher.

    The paper's server generalized from one queue pair to N (§IV-C at
    fleet scale): a :class:`~repro.ipc.listener.Listener` accepts client
    registrations and mints each one a dedicated transport; ``reactors``
    :class:`~repro.ipc.reactor.Reactor` shards multiplex them (clients
    partitioned round-robin at accept time — one drain loop stops being
    the serving ceiling) with round-robin fairness inside each shard; and
    every drained request is fed to *one* shared
    :class:`RequestDispatcher`, so pipelined requests arriving from
    **different processes** inside the batching window are packed into a
    single handler call (cross-client batch formation) and the results are
    demultiplexed back to the right transports by completion callbacks.

    **SLO serving**: requests carrying the reserved priority/deadline
    header keys (:data:`~repro.ipc.channel.PRIO_KEY` /
    :data:`~repro.ipc.channel.DEADLINE_KEY` — set by
    :meth:`RemoteDispatcherClient.request`) are drained, batched, and
    executed in lane order; the dispatcher sheds requests its service
    model predicts past deadline (counted + immediate error reply), the
    per-lane :class:`~repro.obs.metrics.SLOTracker` records latency and
    misses, and a :class:`~repro.ft.monitor.SLOMonitor` watchdog
    evaluates rule bounds over the live metrics plane
    (``fabric.monitor.check()``).  ``default_deadline_ms`` applies a
    server-side deadline (from arrival) to requests that carry none.

    The large-message datapath is transparent here: a client request (or a
    server reply) at/over ``policy.heap_threshold_bytes`` rides the
    connection's bulk-heap extents instead of a ring slot, so request and
    reply sizes are bounded by heap geometry (``spec.heap_extents ×
    spec.heap_extent_bytes`` per direction), not by ``data_slot_bytes``.

    Teardown order matters and is owned by :meth:`close` (one ``with``
    block instead of a tuple of things to unwind): stop accepting, stop
    the sweep, flag every client, close transports, then the dispatcher.
    """

    def __init__(self, dispatcher: RequestDispatcher,
                 name: Optional[str] = None,
                 spec: TransportSpec = TransportSpec(),
                 policy: Optional[OffloadPolicy] = None,
                 latency: Optional[LatencyModel] = None,
                 max_clients: int = 64,
                 max_drain_per_sweep: int = 8,
                 max_inflight: int = 16,
                 reply_timeout_s: Optional[float] = None,
                 own_dispatcher: bool = False,
                 reactors: int = 1,
                 default_deadline_ms: Optional[float] = None):
        from repro.ipc.listener import Listener
        from repro.ipc.reactor import Reactor

        self.dispatcher = dispatcher
        self.policy = policy or dispatcher.policy
        self.reply_timeout_s = (reply_timeout_s if reply_timeout_s is not None
                                else self.policy.retry.reply_timeout_s)
        self._own_dispatcher = own_dispatcher
        # server-side deadline applied (from arrival time) to requests that
        # carry none of their own — 0 disables
        self.default_deadline_ns = int((default_deadline_ms or 0) * 1e6)
        # sharded reactors: N independent drain loops, clients partitioned
        # round-robin at accept time so one sweep thread stops being the
        # serving ceiling; shard 0 doubles as the legacy ``.reactor`` view
        self.reactors = [
            Reactor(self.policy, on_messages=self._on_messages,
                    max_drain_per_sweep=max_drain_per_sweep,
                    max_inflight=max_inflight)
            for _ in range(max(1, reactors))]
        self.reactor = self.reactors[0]
        self._accept_lock = threading.Lock()
        self._next_shard = 0
        self.listener = Listener(name, spec, self.policy, latency,
                                 max_clients=max_clients,
                                 on_accept=self._accept)
        # unified metrics plane: every stats surface in the fabric behind
        # one flat snapshot, plus the per-request SLO monitor (previously
        # orphaned ft/monitor.py + core/latency.py, now fed by replies)
        self.slo = SLOTracker(latency or getattr(dispatcher, "latency", None))
        self.metrics = MetricsRegistry()
        self.metrics.register("reactor", self._reactor_stats)
        self.metrics.register("dispatcher", lambda: self.dispatcher.stats)
        self.metrics.register("slo", self.slo)
        self.metrics.register(
            "listener", lambda: {"accepted": self.listener.accepted,
                                 "clients": sum(len(r)
                                                for r in self.reactors)})
        # live SLO watchdog over the metrics plane (ft/monitor.SLOMonitor):
        # rules read the same flat keys metrics.snapshot() exposes
        self.monitor = SLOMonitor(self.metrics)
        if self.default_deadline_ns:
            self.monitor.add_rule("slo.p95_ms",
                                  self.default_deadline_ns / 1e6)
        self.metrics.register("slo_monitor", self.monitor)
        # hardware-witness plane: per-phase counter totals (insn/byte,
        # LLC misses, ctx switches) land in the same flat snapshot under
        # hw.* when profiling is enabled; a child fabric spawned by a
        # profiling parent inherits enablement through the environment
        _hw.maybe_enable_from_env()
        self.metrics.register("hw", _hw.snapshot)
        self._closed = False

    @property
    def name(self) -> str:
        """The rendezvous name clients connect to."""
        return self.listener.name

    # -- sharding ---------------------------------------------------------------
    def _accept(self, transport: ShmTransport) -> None:
        """Accept-time partitioning: each new client lands on one reactor
        shard (round-robin — balanced under churn without rebalancing
        live connections, which would break the per-ring SPSC contract),
        its lane seeded from the registration hint so the very first
        sweep already drains it in lane order."""
        with self._accept_lock:
            shard = self._next_shard
            self._next_shard = (self._next_shard + 1) % len(self.reactors)
        conn = self.reactors[shard].add(transport)
        lane = (getattr(transport, "accept_meta", None) or {}).get("lane", 0)
        if isinstance(lane, int) and not isinstance(lane, bool):
            conn.lane = lane

    def _all_connections(self) -> list:
        """Live connections across every reactor shard."""
        return [c for r in self.reactors for c in r.connections()]

    def _reactor_stats(self) -> dict:
        """Reactor counters summed across shards (+ the shard count)."""
        agg: dict = {}
        for r in self.reactors:
            for k, v in vars(r.stats).items():
                agg[k] = agg.get(k, 0) + v
        agg["shards"] = len(self.reactors)
        return agg

    def _prepare(self, conn, lease) -> Optional[dict]:
        """Reactor thread: turn one drained request lease into a
        dispatcher submit item (or handle it right here: shutdown
        messages and malformed requests never reach the dispatcher).

        ``lease`` is a :class:`~repro.ipc.channel.RecvLease`; under the
        zero-copy datapath its ``tree["data"]`` is a view straight into
        the client's ring slot, and the *dispatcher* releases the lease
        once the payload has been gathered into a batch buffer (or the
        solo execution completed) — the reactor never copies it.
        """
        header = lease.header
        if header.get("shutdown"):
            lease.release()
            conn.done()     # settle accounting; reaped once its flag is seen
            return None
        job_id = header.get("job_id", -1)
        op, mode = header.get("op"), header.get("mode", "sync")
        # SLO wire meta: strip the reserved lane/deadline keys before the
        # header reaches any handler; a request without its own deadline
        # inherits the fabric default (clocked from arrival)
        priority = header.pop(PRIO_KEY, 0)
        if not isinstance(priority, int) or isinstance(priority, bool):
            priority = 0
        deadline_ns = header.pop(DEADLINE_KEY, 0)
        if not isinstance(deadline_ns, int) or isinstance(deadline_ns, bool):
            deadline_ns = 0
        if not deadline_ns and self.default_deadline_ns:
            deadline_ns = time.perf_counter_ns() + self.default_deadline_ns
        # idempotent request id (exactly-once replay after reconnect):
        # stripped here, fed to the dispatcher's dedup window
        dedup = header.pop(DEDUP_KEY, None)
        if not isinstance(dedup, int) or isinstance(dedup, bool):
            dedup = None
        tree = lease.tree
        rid = lease.rid
        t_arr = time.perf_counter()
        req_nbytes = 0              # rebound below once data is extracted

        def reply(_jid: int, out) -> None:
            hdr = ({"job_id": job_id, _trace.RID_KEY: rid} if rid
                   else {"job_id": job_id})
            try:
                if isinstance(out, Exception):
                    hdr["error"] = f"{type(out).__name__}: {out}"
                    conn.reply({}, hdr, timeout_s=self.reply_timeout_s)
                else:
                    hdr["error"] = None
                    conn.reply({"result": np.asarray(out)}, hdr,
                               timeout_s=self.reply_timeout_s)
            finally:
                # SLO clock: reactor delivery -> reply sent (service time);
                # a reply landing past the request's deadline is a counted
                # per-lane miss (distinct from a shed: the work ran, so a
                # shed error reply is never double-counted as a miss)
                miss = (not isinstance(out, DeadlineExceeded)
                        and bool(deadline_ns)
                        and time.perf_counter_ns() > deadline_ns)
                self.slo.observe(time.perf_counter() - t_arr, req_nbytes,
                                 lane=priority, miss=miss)

        try:
            data = tree["data"] if isinstance(tree, dict) else None
            req_nbytes = int(getattr(data, "nbytes", 0) or 0)
            return {"op": op, "data": data,
                    "mode": ExecutionMode(mode),   # validated HERE, not
                    "on_complete": reply,          # mid-batch in submit_many
                    "rid": rid, "dedup": dedup,
                    "priority": priority, "deadline_ns": deadline_ns,
                    "lease": lease if lease.held else None}
        except Exception as e:
            # malformed request (missing data, bad mode string, ...): tell
            # the client instead of letting it time out.  reply() settles
            # the connection accounting in its finally, so swallow any
            # send failure here rather than re-settling in the reactor.
            lease.release()
            try:
                reply(job_id, e)
            except Exception:
                pass
            return None

    def _on_messages(self, conn, leases) -> None:
        """Reactor thread: feed one drained batch — e.g. a client's whole
        coalesced frame — into the dispatcher as one ``submit_many``, so
        K wire-microbatched requests enter the batching window together."""
        if _inject._PLANE is not None:
            # replication pulls (__ckpt.* ops from a warm standby) drain
            # through this same path but must not advance the crash
            # schedule: the drill is indexed against the *serving* request
            # stream, and standby sync cadence would make it nondeterministic
            serving = any(
                not str(lease.header.get("op", "")).startswith("__ckpt.")
                for lease in leases)
            if serving and _inject.fire("worker.crash") is not None:
                # hard process death mid-batch — the chaos drill the
                # supervisor and reconnecting clients exist for (no
                # cleanup on purpose)
                os._exit(23)
        items = [it for it in (self._prepare(conn, lease)
                               for lease in leases) if it is not None]
        if items:
            self.dispatcher.submit_many(items)

    def start(self) -> "ServingFabric":
        """Begin accepting and serving (all in daemon threads)."""
        for r in self.reactors:
            r.start()
        self.listener.start()
        return self

    def stats(self) -> dict:
        """Fabric-level counters: listener, reactor (summed over shards),
        per-client (including each connection's full transport stats —
        channel, rings, heap, governor), dispatcher, and the request SLO
        snapshot.  The ``metrics`` key is the same data as one flat
        dot-keyed dict (the :class:`~repro.obs.metrics.MetricsRegistry`
        view).  With one shard client keys are the bare cids (the
        pre-sharding shape); with several they are ``"s<shard>c<cid>"``
        (cids are only unique within a shard)."""
        if len(self.reactors) == 1:
            clients = {c.cid: {"received": c.received, "replied": c.replied,
                               "inflight": c.inflight, "lane": c.lane,
                               "transport": c.transport.stats()}
                       for c in self.reactor.connections()}
        else:
            clients = {f"s{si}c{c.cid}": {
                           "received": c.received, "replied": c.replied,
                           "inflight": c.inflight, "lane": c.lane,
                           "transport": c.transport.stats()}
                       for si, r in enumerate(self.reactors)
                       for c in r.connections()}
        return {
            "accepted": self.listener.accepted,
            "reactor": self._reactor_stats(),
            "clients": clients,
            "dispatcher": vars(self.dispatcher.stats),
            "slo": self.slo.snapshot(),
            "metrics": self.metrics.snapshot(),
        }

    def close(self) -> None:
        """Tear down in dependency order; safe to call twice."""
        if self._closed:
            return
        self._closed = True
        self.listener.close()               # no new clients
        for conn in self._all_connections():
            conn.transport.announce_close()  # unblock client-side waits
        for r in self.reactors:
            r.close()                       # stop sweeps, close transports
        if self._own_dispatcher:
            self.dispatcher.close()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()


class ReconnectTimeout(ConnectionError, TimeoutError):
    """A :meth:`RemoteDispatcherClient.reconnect` ran out of a
    caller-imposed time budget (e.g. the enclosing query's deadline)
    before any attempt succeeded.  Distinct from the plain
    ``ConnectionError`` of exhausted *attempts* so callers can tell "the
    server never came back within my deadline" (a promotion or restart
    overran it) from "the server is gone"; subclasses both
    ``ConnectionError`` and ``TimeoutError`` so either family of
    handlers still fires."""


class RemoteDispatcherClient:
    """Client-process side: the paper's request/query API over the wire.

    **Crash recovery**: a client minted by :meth:`connect` is resilient
    to server death.  Every request carries an idempotent id
    (``(session_id << 32) | job_id`` under
    :data:`~repro.ipc.channel.DEDUP_KEY`) and is tracked as *unacked*
    until its reply lands; when the transport dies or the server's
    heartbeat goes stale, :meth:`reconnect` re-registers through the
    listener (bounded retries with exponential backoff —
    ``policy.retry``) and resubmits every unacked request.  The server's
    dedup window makes the replay exactly-once: re-executions are
    suppressed and duplicate replies are filtered here (counted in
    ``dup_replies``; requests whose reply never arrives at all are
    counted in ``lost_replies`` when their query finally times out).
    The receiver thread stamps the client-side heartbeat word so the
    server can tell a live-but-idle client from a dead one.
    """

    def __init__(self, transport: ShmTransport,
                 policy: Optional[OffloadPolicy] = None,
                 latency: Optional[LatencyModel] = None,
                 own_transport: bool = False):
        self.transport = transport
        self.policy = policy or transport.policy
        self.latency = latency or transport.latency
        self.queries = QueryHandler()
        self._own_transport = own_transport
        # a client process spawned by a profiling parent profiles too
        # (publish / governor / reply_drain phases), same env handshake
        # as the tracer's
        _hw.maybe_enable_from_env()
        self.lane = 0                      # default priority for request()
        # 32-bit session nonce: scopes idempotent ids to this client life
        self.session_id = int.from_bytes(os.urandom(4), "little") or 1
        self._ids = iter(range(1, 1 << 62))
        self._lock = threading.Lock()
        self._recv_thread: Optional[threading.Thread] = None
        self._stop = threading.Event()
        # reconnect-with-replay state (populated by connect())
        self._listener_name: Optional[str] = None
        self._latency_arg = latency
        self._policy_arg = policy
        self._reconnect_lock = threading.Lock()
        # serializes receiver-thread transport use against the reconnect
        # swap: closing an arena out from under a blocked recv would tear
        # live memoryviews (BufferError) instead of failing cleanly
        self._transport_lock = threading.RLock()
        self._unacked: dict[int, tuple[dict, np.ndarray]] = {}
        self._completed: set[int] = set()
        self._completed_q: deque = deque()
        self._completed_cap = 4 * self.policy.retry.dedup_window
        self.reconnects = 0
        self.retries = 0
        self.dup_replies = 0
        self.lost_replies = 0

    @classmethod
    def connect(cls, listener_name: str,
                policy: Optional[OffloadPolicy] = None,
                latency: Optional[LatencyModel] = None,
                timeout_s: Optional[float] = None,
                lane: int = 0) -> "RemoteDispatcherClient":
        """Register with a :class:`ServingFabric` by rendezvous name and
        return a ready client owning its dedicated transport.  ``lane``
        hints the client's priority class at accept time (the server
        seeds its connection's drain lane before the first request) and
        becomes the default ``priority`` for :meth:`request`.  Default
        ``timeout_s`` is ``policy.retry.connect_timeout_s``."""
        from repro.ipc.listener import connect as fabric_connect
        if timeout_s is None:
            timeout_s = (policy or OffloadPolicy()).retry.connect_timeout_s
        transport = fabric_connect(listener_name, policy=policy,
                                   latency=latency, timeout_s=timeout_s,
                                   meta={"lane": lane} if lane else None)
        client = cls(transport, policy=policy, latency=latency,
                     own_transport=True)
        client.lane = lane
        client._listener_name = listener_name
        return client

    def _ensure_receiver(self) -> None:
        with self._lock:
            if self._recv_thread is None:
                self._recv_thread = threading.Thread(
                    target=self._recv_loop, daemon=True,
                    name="rocket-ipc-cli")
                self._recv_thread.start()

    def _recv_loop(self) -> None:
        poll_s = self.policy.retry.recv_poll_s
        meter = _hw.LoopMeter(_trace.RECV_LOOP)   # thread CPU, when traced
        while not self._stop.is_set():
            if _trace.TRACE.enabled:
                meter.tick()
            failed = False
            with self._transport_lock:
                transport = self.transport
                # reply_drain scope: only drains that actually yield a
                # reply are accounted (a timed-out idle poll is sleep,
                # not drain cost — metering it would swamp the profile)
                c0 = _hw.begin() if _hw.PROF.enabled else None
                try:
                    transport.heartbeat()  # liveness stamp (rate-limited)
                    tree, header = transport.recv(timeout_s=poll_s)
                except TimeoutError:
                    continue
                except Exception:
                    # transport torn down (server death / reconnect swap)
                    failed = True
            if failed:
                # idle until reconnect() installs a fresh transport or we
                # stop — only a reconnectable client keeps the thread alive
                if self._listener_name is None:
                    break
                time.sleep(poll_s)
                continue
            err = header.get("error")
            result = RuntimeError(err) if err else tree["result"]
            rid = header.get(_trace.RID_KEY, 0)
            rid = rid if isinstance(rid, int) else 0
            if _trace.TRACE.enabled and rid:
                _trace.instant(_trace.CLIENT_RECV, rid=rid)
            if c0 is not None:
                _hw.end(c0, "reply_drain", rid=rid,
                        nbytes=getattr(result, "nbytes", 0))
            job_id = header["job_id"]
            with self._lock:
                if job_id in self._completed:
                    # replayed request answered twice (original completed
                    # after the resubmit raced it) — exactly-once delivery
                    # means dropping it here, counted
                    self.dup_replies += 1
                    continue
                self._completed.add(job_id)
                self._completed_q.append(job_id)
                while len(self._completed_q) > self._completed_cap:
                    self._completed.discard(self._completed_q.popleft())
                self._unacked.pop(job_id, None)
            self.queries.complete(job_id, result)
        meter.flush()

    # -- crash recovery -------------------------------------------------------
    def reconnect(self, deadline: Optional[float] = None) -> None:
        """Re-register through the listener and replay unacked requests.

        Bounded attempts (``policy.retry.max_reconnects``) with
        exponential backoff between them; the old transport is closed
        (its arena unlinks once the server reaps it) and every request
        still awaiting a reply is resubmitted with its original
        idempotent id — the server's dedup window turns the replay into
        exactly-once execution.  Raises ``ConnectionError`` when every
        attempt fails; only clients from :meth:`connect` can reconnect.

        ``deadline`` (absolute ``time.perf_counter()``) bounds the
        *cumulative* time spent here: each attempt's connect timeout and
        each backoff sleep are clipped to the remaining budget, and
        exhausting it raises :class:`ReconnectTimeout` — so a recovery
        (e.g. a standby promotion) that overruns the enclosing query's
        deadline surfaces as a typed error instead of over-waiting.
        """
        if self._listener_name is None:
            raise ConnectionError("client has no listener to reconnect to")
        from repro.ipc.listener import connect as fabric_connect
        retry = self.policy.retry

        def remaining_or_raise(last: Optional[Exception]) -> Optional[float]:
            if deadline is None:
                return None
            left = deadline - time.perf_counter()
            if left <= 0:
                raise ReconnectTimeout(
                    f"reconnect to {self._listener_name!r} exceeded its "
                    f"deadline budget") from last
            return left

        with self._reconnect_lock:
            last: Optional[Exception] = None
            for attempt in range(max(1, retry.max_reconnects)):
                left = remaining_or_raise(last)
                timeout_s = (retry.connect_timeout_s if left is None
                             else min(retry.connect_timeout_s, left))
                try:
                    transport = fabric_connect(
                        self._listener_name, policy=self._policy_arg,
                        latency=self._latency_arg,
                        timeout_s=timeout_s,
                        meta={"lane": self.lane} if self.lane else None)
                except Exception as e:
                    last = e
                    left = remaining_or_raise(last)
                    backoff = retry.backoff_s(attempt)
                    time.sleep(backoff if left is None
                               else min(backoff, left))
                    continue
                with self._transport_lock:
                    # swap under the receiver's lock: close must not tear
                    # views out from under a blocked recv
                    old, self.transport = self.transport, transport
                    try:
                        old.close()
                    except Exception:
                        pass
                self.reconnects += 1
                self._resubmit_unacked()
                return
            raise ConnectionError(
                f"reconnect to {self._listener_name!r} failed after "
                f"{retry.max_reconnects} attempts") from last

    def _resubmit_unacked(self) -> None:
        """Replay every request still awaiting a reply, oldest first, on
        the (fresh) transport — same headers, same idempotent ids."""
        with self._lock:
            pending = sorted(self._unacked.items())
        for _job_id, (header, data) in pending:
            self.transport.send({"data": data}, header=dict(header),
                                mode="sync")

    def request(self, op: str, data: np.ndarray,
                mode: ExecutionMode | str | None = None,
                priority: Optional[int] = None,
                deadline_ms: Optional[float] = None):
        """Paper Listing 1: sync returns the result, async/pipelined a
        job id for :meth:`query`.

        ``priority`` selects the request's SLO lane (0 = highest; default
        is the client's ``lane``), ``deadline_ms`` a relative deadline
        stamped as an absolute CLOCK_MONOTONIC wire deadline — both ride
        the META_BINARY header (reserved int tags, no pickle).  A request
        the server sheds or fails comes back as a ``RuntimeError`` whose
        message starts with ``DeadlineExceeded`` from :meth:`query`.
        """
        mode = ExecutionMode(mode) if mode is not None else self.policy.mode
        with self._lock:
            job_id = next(self._ids)
        data = np.asarray(data)
        header = {"job_id": job_id, "op": op, "mode": mode.value,
                  # idempotent request id: lets the server suppress
                  # re-execution when this request is replayed after a
                  # reconnect (session-scoped, so restarts never collide)
                  DEDUP_KEY: (self.session_id << 32)
                  | (job_id & 0xFFFFFFFF)}
        priority = self.lane if priority is None else int(priority)
        if priority:
            header[PRIO_KEY] = priority
        if deadline_ms is not None:
            header[DEADLINE_KEY] = (time.perf_counter_ns()
                                    + int(deadline_ms * 1e6))
        rid = 0
        if _trace.TRACE.enabled:
            # mint the request id HERE — the whole lifecycle (wire, reactor,
            # dispatcher, handler, reply) joins on it across processes
            rid = _trace.mint_rid()
            header[_trace.RID_KEY] = rid
        # all modes go through the receiver thread + QueryHandler: replies
        # are matched by job_id, so concurrent client threads can't steal
        # each other's results off the SPSC rx ring
        self._ensure_receiver()
        self.queries.register(Request(job_id, op, None, mode,
                                      nbytes=int(data.nbytes), rid=rid))
        # track as unacked BEFORE the send: if the transport dies inside
        # send(), the reconnect replay below already covers this request
        with self._lock:
            self._unacked[job_id] = (header, data)
        t0 = _trace.now() if rid else 0
        try:
            self.transport.send({"data": data}, header=header, mode=mode)
            self.transport.heartbeat()
        except (ChannelClosed, TimeoutError, ValueError, OSError):
            if self._listener_name is None:
                raise
            self.reconnect()       # resubmits unacked, this request included
        if rid:
            _trace.emit(_trace.CLIENT_SEND, t0, rid=rid,
                        arg=min(int(data.nbytes), 0xFFFFFFFF))
        if mode == ExecutionMode.SYNC:
            return self.query(job_id)
        return job_id

    def query(self, job_id: int, timeout: Optional[float] = None):
        """Blocking wait for one job's result (raises server errors).

        Publishes any open coalesced frame first: a request still sitting
        in one must reach the wire before we block on its reply.  (Only
        the frame — a full ``flush()`` would block on, and re-raise the
        failures of, unrelated in-flight sends from other threads.)

        Default timeout is ``policy.retry.query_timeout_s``.  A client
        from :meth:`connect` waits in heartbeat-sized slices: when the
        server's heartbeat goes stale mid-wait it reconnects and replays
        before resuming the wait, so one server crash costs recovery
        time, not the whole query timeout.  A reply that never arrives
        even so is counted in ``lost_replies``.
        """
        if timeout is None:
            timeout = self.policy.retry.query_timeout_s
        try:
            self.transport.data.flush_open_frame()
        except (ChannelClosed, ValueError, OSError):
            if self._listener_name is None:
                raise
            self.reconnect()
        # the wait emits its client.query_wait span in QueryHandler.query
        deadline = time.perf_counter() + timeout
        retry = self.policy.retry
        # wait in heartbeat-interval slices (not stale_s slices): the
        # staleness check below only runs at slice boundaries, so a
        # coarser slice would quantize failure detection to up to
        # 2x stale_s depending on heartbeat phase at the crash
        slice_s = max(retry.heartbeat_interval_s, 0.05)
        resubmits = 0
        # single-request resubmit patience: a slice is too short to
        # conclude a reply was dropped (it may simply be in flight),
        # so re-send only after a full stale window of silence
        last_send = time.perf_counter()
        while True:
            remaining = deadline - time.perf_counter()
            if remaining <= 0:
                with self._lock:
                    lost = self._unacked.pop(job_id, None) is not None
                if lost:
                    self.lost_replies += 1
                raise TimeoutError(f"job {job_id} timed out")
            try:
                out = self.queries.query(job_id, min(remaining, slice_s))
                break
            except TimeoutError:
                # mid-wait failure detection: a stale server heartbeat
                # (or dead transport) triggers reconnect + replay here
                # rather than burning the rest of the timeout
                if self._listener_name is None:
                    continue
                try:
                    stale = self.transport.peer_stale()
                except Exception:
                    stale = True       # transport already torn down
                if stale:
                    try:
                        # bound the cumulative reconnect wait by this
                        # query's own deadline: a promotion/restart
                        # that overruns it becomes a typed error now,
                        # not a silent over-wait
                        self.reconnect(deadline=deadline)
                    except ReconnectTimeout:
                        with self._lock:
                            lost = (self._unacked.pop(job_id, None)
                                    is not None)
                        if lost:
                            self.lost_replies += 1
                        raise
                    except ConnectionError:
                        pass
                    last_send = time.perf_counter()  # replay counts
                    continue
                # server alive but this request never answered — the
                # request (or its reply) was dropped in transit (e.g.
                # quarantined as corrupt).  Bounded single-request
                # resubmit, idempotent by dedup id, and only after a
                # full stale window of silence since the last send —
                # one elapsed slice just means the reply is in flight.
                if (time.perf_counter() - last_send
                        < retry.heartbeat_stale_s):
                    continue
                with self._lock:
                    entry = self._unacked.get(job_id)
                if entry is not None \
                        and resubmits < retry.max_reconnects:
                    hdr, payload = entry
                    try:
                        self.transport.send({"data": payload},
                                            header=dict(hdr),
                                            mode="sync")
                    except Exception:
                        continue
                    resubmits += 1
                    self.retries += 1
                    last_send = time.perf_counter()
        if isinstance(out, Exception):
            raise out
        return out

    def close(self) -> None:
        """Stop the receiver, tell the server we're leaving, and (when the
        client owns its transport, i.e. it came from :meth:`connect`) close
        it — the server reaps the connection and unlinks the arena."""
        retry = self.policy.retry
        self._stop.set()
        if self._recv_thread is not None:
            self._recv_thread.join(timeout=retry.join_timeout_s)
        try:
            self.transport.send({}, header={"job_id": -1, "shutdown": True},
                                mode="sync",
                                timeout_s=retry.shutdown_send_timeout_s)
        except (TimeoutError, ChannelClosed, ValueError):
            pass
        if self._own_transport:
            self.transport.close()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()
