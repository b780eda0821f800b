"""Serving runtime: batched prefill/decode with persistent device cache slots.

ROCKET integration:
- the :class:`~repro.core.dispatcher.RequestDispatcher` front-end batches
  requests (pipelined mode) before they hit the device — the paper's
  application-level request batching;
- KV caches are *donated* through jit (persistent queue-pair buffers: the
  allocation is reused every decode step, no re-mapping);
- host→device prompt transfer goes through the tier-1 engine policy.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.core.dispatcher import RequestDispatcher
from repro.core.engine import AsyncTransferEngine
from repro.core.latency import LatencyModel
from repro.core.policy import ExecutionMode, OffloadPolicy
from repro.models.registry import ModelAPI
from repro.obs import trace as _trace


@dataclass(frozen=True)
class ServeConfig:
    max_len: int = 2048
    max_batch: int = 8
    max_new_tokens: int = 32
    greedy: bool = True


class BatchedServer:
    """Batch-synchronous generation server over a fixed slot count."""

    def __init__(self, model: ModelAPI, params, scfg: ServeConfig,
                 policy: OffloadPolicy = OffloadPolicy()):
        self.model = model
        self.params = params
        self.scfg = scfg
        self.policy = policy
        self.engine = AsyncTransferEngine(policy)
        # named steps: compile events and profiler traces report them as
        # jit(prefill) / jit(decode)
        def prefill(params, batch):
            return model.prefill(params, batch, max_len=scfg.max_len)

        def decode(params, cache, tok):
            return model.decode_step(params, cache, tok)

        self._prefill = jax.jit(prefill)
        # cache donated: the persistent decode buffer is reused in place
        self._decode = jax.jit(decode, donate_argnums=(1,))
        self.stats = {"requests": 0, "batches": 0, "tokens_out": 0}

    # -- core batched generation ------------------------------------------------
    def generate_batch(self, batch: dict, new_tokens: Optional[int] = None
                       ) -> np.ndarray:
        n_new = new_tokens or self.scfg.max_new_tokens
        # traced: one span per phase, nested in serve.generate_batch; the
        # prefill and decode spans time the dispatch, the sync the wait
        t0 = _trace.now() if _trace.TRACE.enabled else 0
        dev_batch = self.engine.submit(batch).get()
        t1 = _trace.now() if t0 else 0
        logits, cache = self._prefill(self.params, dev_batch)
        outs = []
        tok = jnp.argmax(logits[:, -1, :], axis=-1).astype(jnp.int32)[:, None]
        outs.append(tok)
        t2 = _trace.now() if t0 else 0
        for _ in range(n_new - 1):
            logits, cache = self._decode(self.params, cache, tok)
            tok = jnp.argmax(logits[:, -1, :], axis=-1).astype(jnp.int32)[:, None]
            outs.append(tok)
        t3 = _trace.now() if t0 else 0
        result = np.asarray(jnp.concatenate(outs, axis=1))
        self.stats["batches"] += 1
        self.stats["tokens_out"] += result.size
        if t0:
            t4 = _trace.now()
            rows = result.shape[0]
            for kind, a, b in ((_trace.SERVE_H2D, t0, t1),
                               (_trace.SERVE_PREFILL, t1, t2),
                               (_trace.SERVE_DECODE, t2, t3),
                               (_trace.SERVE_SYNC, t3, t4),
                               (_trace.SERVE_BATCH, t0, t4)):
                _trace.emit(kind, a, arg=rows, t1=b)
        return result

    # -- diskless checkpoint/restore ---------------------------------------------
    def state_snapshot(self) -> tuple:
        """``(tree, extra)`` for a :class:`repro.checkpoint.ShardCodec` /
        :class:`repro.checkpoint.ReplicationSource`: the parameter pytree
        pulled to host memory plus the serving counters as picklable side
        state.  Byte-exact — :meth:`restore_state` of the encoded shards
        reproduces the params bit-for-bit."""
        host = jax.tree_util.tree_map(
            lambda x: np.asarray(jax.device_get(x)), self.params)
        return host, {"stats": dict(self.stats)}

    def restore_state(self, tree, extra: Optional[dict] = None) -> None:
        """Adopt a replicated/decoded snapshot: install the parameter
        pytree (device placement happens lazily on first jit call) and
        the serving counters, so a promoted replica's numbers continue
        the primary's, not restart from zero."""
        self.params = tree
        if extra and "stats" in extra:
            self.stats.update(extra["stats"])

    # -- request-level API (dispatcher integration) ------------------------------
    def make_dispatcher(self, latency: Optional[LatencyModel] = None,
                        workers: int = 1) -> RequestDispatcher:
        d = RequestDispatcher(self.policy, latency, workers=workers)

        def single(data: np.ndarray) -> np.ndarray:
            self.stats["requests"] += 1
            return self.generate_batch(self._pack([data]))[0]

        def batched(datas: list[np.ndarray]) -> list[np.ndarray]:
            self.stats["requests"] += len(datas)
            out = self.generate_batch(self._pack(datas))
            return [out[i] for i in range(len(datas))]

        def batched_slab(slab: np.ndarray, shapes) -> list[np.ndarray]:
            # single-copy datapath: the dispatcher's batch-formation gather
            # already left-aligned + zero-padded every prompt into ``slab``
            # — exactly what _pack would build — so wrap it without another
            # per-row packing copy
            self.stats["requests"] += len(shapes)
            out = self.generate_batch(self._wrap(slab))
            return [out[i] for i in range(len(shapes))]

        d.register_handler("generate", single, batch_fn=batched,
                           slab_fn=batched_slab)
        return d

    # -- cross-process serving (repro.ipc) ---------------------------------------
    def serve_over_ipc(self, name: Optional[str] = None,
                       latency: Optional[LatencyModel] = None,
                       data_slot_bytes: int = 2 << 20,
                       heap_extent_bytes: int = 1 << 20,
                       heap_extents: int = 32,
                       max_clients: int = 64,
                       reactors: int = 1,
                       default_deadline_ms: Optional[float] = None,
                       replicate: bool = False,
                       shard_bytes: int = 1 << 20):
        """Expose the dispatcher to any number of client *processes* over
        the multi-client shared-memory fabric.

        Returns a started :class:`repro.ipc.ServingFabric` — use it as a
        context manager (one ``with`` tears down listener, reactor,
        per-client transports, and the dispatcher in order).  Clients join
        with ``RemoteDispatcherClient.connect(fabric.name)`` and use the
        paper's request/query API; pipelined requests from different
        clients are batched into single model calls.

        Slots only have to fit *sub-threshold* messages now: prompts or
        replies at/over ``policy.heap_threshold_bytes`` ride each
        connection's bulk heap (``heap_extents × heap_extent_bytes`` per
        direction; ``heap_extents=0`` disables it), so per-client shared
        memory stays small without capping the payload size.

        SLO serving: ``reactors`` shards the drain loop (clients are
        partitioned across shards at accept time; the dispatcher gets a
        matching worker pool so shards execute concurrently), and
        ``default_deadline_ms`` stamps a deadline on every request that
        arrives without one, arming the fabric's SLO monitor.

        ``replicate=True`` attaches a
        :class:`repro.checkpoint.ReplicationSource` over
        :meth:`state_snapshot` (sharded at ``shard_bytes``), so a warm
        standby (:class:`repro.ft.StandbyReplica`) can mirror this
        server's params + dispatcher state through the same fabric; the
        source is exposed as ``fabric.replication``.
        """
        from repro.ipc import ServingFabric
        from repro.ipc.transport import TransportSpec

        dispatcher = self.make_dispatcher(latency, workers=max(1, reactors))
        fabric = ServingFabric(
            dispatcher, name=name,
            spec=TransportSpec(data_slot_bytes=data_slot_bytes,
                               heap_extent_bytes=heap_extent_bytes,
                               heap_extents=heap_extents),
            policy=self.policy, latency=latency, max_clients=max_clients,
            own_dispatcher=True, reactors=reactors,
            default_deadline_ms=default_deadline_ms)
        fabric.metrics.register("server", lambda: self.stats)
        if replicate:
            from repro.checkpoint import ReplicationSource
            fabric.replication = ReplicationSource(
                self.state_snapshot, shard_bytes=shard_bytes
            ).attach(dispatcher)
        return fabric.start()

    def _pack(self, prompts: list[np.ndarray]) -> dict:
        """Left-align prompts into a fixed (B, S) slab (persistent shape)."""
        s = max(int(p.shape[-1]) for p in prompts)
        b = len(prompts)
        toks = np.zeros((b, s), np.int32)
        for i, p in enumerate(prompts):
            toks[i, : p.shape[-1]] = p
        return self._wrap(toks)

    def _wrap(self, toks: np.ndarray) -> dict:
        """Model-input dict around an already-packed (B, S) token slab."""
        toks = np.ascontiguousarray(toks.astype(np.int32, copy=False))
        b, s = toks.shape
        batch = {"tokens": toks}
        cfg = self.model.cfg
        if cfg.family == "audio":
            batch["frame_embeds"] = np.zeros((b, s, cfg.d_model), np.float32)
        if cfg.family == "vlm":
            batch["patch_embeds"] = np.zeros(
                (b, cfg.num_patches, cfg.d_model), np.float32)
        return batch

    def close(self):
        self.engine.close()
