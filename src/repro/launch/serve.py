"""Serving driver: batched generation server with the ROCKET dispatcher.

CPU-scale example:
  PYTHONPATH=src python -m repro.launch.serve --arch granite-8b --smoke \
      --requests 16 --mode pipelined
"""
from __future__ import annotations

import argparse
import time

import jax
import numpy as np

from repro.configs import get_config, get_smoke_config
from repro.core import ExecutionMode, OffloadPolicy
from repro.launch.cache import use_compile_cache
from repro.models import build_model
from repro.serve import BatchedServer, ServeConfig


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--requests", type=int, default=16)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--new-tokens", type=int, default=8)
    ap.add_argument("--max-batch", type=int, default=4)
    ap.add_argument("--mode", default="pipelined",
                    choices=["sync", "async", "pipelined"])
    args = ap.parse_args()
    use_compile_cache()

    cfg = get_smoke_config(args.arch) if args.smoke else get_config(args.arch)
    model = build_model(cfg)
    params = model.init(jax.random.key(0))

    scfg = ServeConfig(max_len=args.prompt_len + args.new_tokens + cfg.num_patches,
                       max_batch=args.max_batch, max_new_tokens=args.new_tokens)
    policy = OffloadPolicy(mode=ExecutionMode(args.mode),
                           max_batch=args.max_batch,
                           offload_threshold_bytes=1 << 12)
    server = BatchedServer(model, params, scfg, policy)
    rng = np.random.default_rng(0)

    with server.make_dispatcher() as dispatcher:
        t0 = time.perf_counter()
        if args.mode == "sync":
            outs = [dispatcher.request(
                "generate",
                rng.integers(0, cfg.vocab_size, args.prompt_len).astype(np.int32),
                mode="sync") for _ in range(args.requests)]
        else:
            jids = [dispatcher.request(
                "generate",
                rng.integers(0, cfg.vocab_size, args.prompt_len).astype(np.int32),
                mode=args.mode) for _ in range(args.requests)]
            outs = [dispatcher.query(j) for j in jids]
        dt = time.perf_counter() - t0

    n_tok = sum(o.size for o in outs)
    print(f"{args.requests} requests, {n_tok} tokens in {dt:.2f}s "
          f"({n_tok / dt:.1f} tok/s, {dt / args.requests * 1e3:.1f} ms/req)")
    print(f"server stats: {server.stats}")
    print(f"dispatcher: batches={dispatcher.stats.batches} "
          f"mean_batch={dispatcher.stats.mean_batch:.2f} "
          f"query_polls={dispatcher.stats.query_polls}")
    server.close()


if __name__ == "__main__":
    main()
