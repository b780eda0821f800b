"""Chip smoke: serve granite-moe-1b-a400m at full width over the IPC fabric
on one TPU, and check what comes back.

  python chip_smoke.py

The main path as a user drives it: three client *processes* connect to a
``BatchedServer.serve_over_ipc()`` fabric by rendezvous name and send
seeded 128-token prompts; the server runs prefill and decode on the chip.

- Phase A (sync): each client sends a few ``mode="sync"`` requests, one
  client at a time, so each is a batch of one.  Every reply must be
  byte-identical to ``server.generate_batch(server._pack([p]))`` run
  directly in this process on the same chip.  (Batch size one on both
  sides: MoE capacity dropping makes a row depend on its batchmates.)
- Phase B (pipelined): all clients send together.  Replies must have the
  right shape with tokens in ``[0, vocab)``, and the dispatcher must have
  batched across clients (phase B's mean batch > 1).

Only this process touches the chip: each client reports whether it
initialized a JAX backend, and any that did fails the run.  The model is
at its published widths and full depth with random weights from
``--seed``.  Earlier lines print param bytes, compile seconds, first-request
latency, server and dispatcher stats and peak device memory, as
information.  The last line is one JSON object:
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
Any failed check exits non-zero and prints no such line.

``--platform cpu --smoke`` rehearses the same flow on the CPU backend with
the reduced config (set ``JAX_PLATFORMS=cpu``).
"""
import argparse
import json
import multiprocessing as mp
import os
import queue
import sys
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

ARCH = "granite-moe-1b-a400m"
N_CLIENTS = 3
PROMPT_LEN = 128         # one length for every prompt: see ROADMAP (mixed
                         # lengths in one batch give wrong tokens today)
NEW_TOKENS = 16
MAX_LEN = 256
MAX_BATCH = 8
SYNC_PER_CLIENT = 2
PIPE_PER_CLIENT = 8
QUERY_TIMEOUT_S = 600.0  # a reply can wait behind a compile
PHASE_TIMEOUT_S = 900.0
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


def _policy():
    from repro.core.policy import OffloadPolicy, RetryPolicy
    return OffloadPolicy(max_batch=MAX_BATCH,
                         retry=RetryPolicy(query_timeout_s=QUERY_TIMEOUT_S))


def _prompts(seed: int, client: int, phase: str, n: int, vocab: int):
    rng = np.random.default_rng([seed, client, ord(phase)])
    return list(rng.integers(0, vocab, (n, PROMPT_LEN), dtype=np.int32))


def _client(name, idx, seed, vocab, sync_lock, go, results):
    """One client process: phase A under ``sync_lock``, then phase B once
    the parent sets ``go``.  Never touches a JAX backend."""
    from jax._src import xla_bridge
    from repro.ipc import RemoteDispatcherClient

    client = RemoteDispatcherClient.connect(name, policy=_policy())
    try:
        sync_out, starts, lats = [], [], []
        with sync_lock:
            for p in _prompts(seed, idx, "A", SYNC_PER_CLIENT, vocab):
                starts.append(time.time())
                t0 = time.perf_counter()
                sync_out.append(np.asarray(
                    client.request("generate", p, mode="sync")))
                lats.append(time.perf_counter() - t0)
        results.put(("A", idx, sync_out, starts, lats))
        if not go.wait(PHASE_TIMEOUT_S):
            raise TimeoutError("parent never started phase B")
        pipe = _prompts(seed, idx, "B", PIPE_PER_CLIENT, vocab)
        jids = [client.request("generate", p, mode="pipelined") for p in pipe]
        pipe_out = [np.asarray(client.query(j, timeout=QUERY_TIMEOUT_S))
                    for j in jids]
    finally:
        client.close()
    results.put(("B", idx, pipe_out, client.retries,
                 xla_bridge.backends_are_initialized()))


def _check(cond, what: str) -> None:
    if not cond:
        raise SystemExit(f"chip_smoke FAILED: {what}")


def _collect(results, procs, phase: str) -> dict:
    """Wait for every client's ``phase`` message; a dead client fails."""
    got = {}
    deadline = time.perf_counter() + PHASE_TIMEOUT_S
    while len(got) < len(procs):
        try:
            msg = results.get(timeout=1.0)
        except queue.Empty:
            dead = [p.exitcode for p in procs if p.exitcode not in (None, 0)]
            _check(not dead, f"client exited with {dead} in phase {phase}")
            _check(time.perf_counter() < deadline, f"phase {phase} timed out")
            continue
        _check(msg[0] == phase, f"message {msg[0]} during phase {phase}")
        got[msg[1]] = msg[2:]
    return got


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--platform", default="tpu")
    ap.add_argument("--smoke", action="store_true",
                    help="reduced config, for a CPU rehearsal")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    import jax
    devices = jax.devices(args.platform)    # raises when there is no chip
    dev = devices[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices)}
    print(f"device: {json.dumps(device)}", flush=True)
    _check(jax.devices()[0] == dev, f"default device is {jax.devices()[0]}")

    from repro.configs import get_config, get_smoke_config
    from repro.launch.cache import use_compile_cache
    from repro.models import build_model
    from repro.serve import BatchedServer, ServeConfig

    print(f"compile cache: {use_compile_cache()}", flush=True)
    compile_s: dict = {}

    def on_event(event, secs, **kw):
        if event == COMPILE_EVENT:
            name = kw.get("fun_name", "?")
            compile_s[name] = compile_s.get(name, 0.0) + secs
    jax.monitoring.register_event_duration_secs_listener(on_event)

    cfg = (get_smoke_config if args.smoke else get_config)(ARCH)
    model = build_model(cfg)

    def init(key):                      # named: compiles as jit(init)
        return model.init(key)
    params = jax.jit(init)(jax.random.key(args.seed))
    leaves = jax.tree.leaves(jax.block_until_ready(params))
    print(f"model: {cfg.name} layers={cfg.num_layers} d_model={cfg.d_model} "
          f"experts={cfg.num_experts}x{cfg.d_ff} top{cfg.num_experts_per_token} "
          f"vocab={cfg.vocab_size} params={sum(x.size for x in leaves)} "
          f"param_bytes={sum(x.nbytes for x in leaves)} "
          f"dtype={leaves[0].dtype}", flush=True)
    _check(all(x.devices() == {dev} for x in leaves), "params not on device")

    policy = _policy()
    server = BatchedServer(
        model, params, ServeConfig(max_len=MAX_LEN, max_batch=MAX_BATCH,
                                   max_new_tokens=NEW_TOKENS), policy)
    ctx = mp.get_context("spawn")
    sync_lock, go, results = ctx.Lock(), ctx.Event(), ctx.Queue()
    procs = []
    try:
        with server.serve_over_ipc() as fabric:
            procs = [ctx.Process(target=_client, args=(
                fabric.name, i, args.seed, cfg.vocab_size, sync_lock, go,
                results)) for i in range(N_CLIENTS)]
            for p in procs:
                p.start()

            phase_a = _collect(results, procs, "A")
            first = min((s[0], lat[0]) for _, s, lat in phase_a.values())
            print(f"first request latency: {first[1]:.3f} s (sync, batch 1, "
                  f"compile included)", flush=True)
            d0 = dict(vars(fabric.dispatcher.stats))

            t0 = time.perf_counter()
            go.set()
            phase_b = _collect(results, procs, "B")
            wall_b = time.perf_counter() - t0
            d1 = dict(vars(fabric.dispatcher.stats))
            for p in procs:
                p.join(timeout=60)
                _check(p.exitcode == 0, f"client exit code {p.exitcode}")
            fstats = fabric.stats()
        n_batches = d1["batches"] - d0["batches"]
        mean_b = (d1["batched_requests"] - d0["batched_requests"]) \
            / max(n_batches, 1)
        print(f"server stats: {json.dumps(server.stats)}", flush=True)
        print(f"dispatcher stats: {json.dumps(fstats['dispatcher'])}",
              flush=True)
        print(f"fabric: accepted={fstats['accepted']} "
              f"reactor={json.dumps(fstats['reactor'], default=str)}",
              flush=True)

        # phase A: each sync reply == the same prompt run directly here
        n_sync = 0
        for idx, (outs, _, _) in sorted(phase_a.items()):
            prompts = _prompts(args.seed, idx, "A", SYNC_PER_CLIENT,
                               cfg.vocab_size)
            for p, out in zip(prompts, outs):
                ref = server.generate_batch(server._pack([p]))[0]
                _check(out.dtype == ref.dtype and out.shape == ref.shape
                       and out.tobytes() == ref.tobytes(),
                       f"client {idx} sync reply {out} != direct {ref}")
                n_sync += 1
        _check(n_sync == N_CLIENTS * SYNC_PER_CLIENT, "sync replies missing")
        print(f"phase A: {n_sync} sync replies byte-identical to direct "
              f"generate_batch", flush=True)

        # phase B: shapes, token range, batching across clients
        n_pipe = 0
        for idx, (outs, retries, backend) in sorted(phase_b.items()):
            _check(not backend, f"client {idx} initialized a JAX backend")
            _check(len(outs) == PIPE_PER_CLIENT, f"client {idx} replies")
            for out in outs:
                _check(out.shape == (NEW_TOKENS,) and out.dtype == np.int32
                       and out.min() >= 0 and out.max() < cfg.vocab_size,
                       f"client {idx} bad reply {out!r}")
                n_pipe += 1
        print(f"phase B: {n_pipe} pipelined replies ok in {wall_b:.3f} s; "
              f"batches={n_batches} mean_batch={mean_b:.2f}; client retries="
              f"{[phase_b[i][1] for i in sorted(phase_b)]}", flush=True)
        _check(mean_b > 1, f"phase B mean_batch {mean_b} <= 1")
        print(f"clients: {N_CLIENTS} processes, none initialized a JAX "
              f"backend", flush=True)
    finally:
        for p in procs:
            if p.is_alive():
                p.terminate()
            p.join(timeout=10)
        server.close()

    print("compile seconds: " + json.dumps(
        {k: round(v, 3) for k, v in sorted(compile_s.items())}), flush=True)
    print(f"compile: init_s={compile_s.get('jit(init)', 0.0):.3f} "
          f"prefill_s={compile_s.get('jit(prefill)', 0.0):.3f} "
          f"decode_s={compile_s.get('jit(decode)', 0.0):.3f} "
          f"total_s={sum(compile_s.values()):.3f}", flush=True)
    mem = dev.memory_stats() or {}
    print(f"peak_bytes_in_use: {mem.get('peak_bytes_in_use', 'not reported')}",
          flush=True)
    print(json.dumps({"ok": True, "device": device}), flush=True)


if __name__ == "__main__":
    main()
