"""One client process of a run: sends its share of the mix through the
fabric and stamps every request.

Spawned by the harness; it never initializes a JAX backend (the chip
belongs to the server process), and reports whether one was initialized
all the same.  Each request is sent at its due time whatever is
outstanding.  Replies are awaited in send order, which is the order the
server answers one client in.
"""
from __future__ import annotations

import math
import queue
import threading
import time

import numpy as np

#: a reply due in the window is awaited this long past its close
GRACE_S = 60.0


def client_main(idx: int, fabric_name: str, max_batch: int, mix: dict,
                seed: int, vocab: int, plan, ready, go, t0_value,
                seconds: float, results) -> None:
    """``plan``: list of ``(k, due offset)``.  Puts one result dict on
    ``results`` before exiting."""
    from jax._src import xla_bridge

    from bench import gen
    from repro.core.policy import OffloadPolicy
    from repro.ipc import RemoteDispatcherClient

    length = mix["prompt_len"]
    prompts = {k: gen.prompt(seed, idx, k, length, vocab) for k, _ in plan}
    client = RemoteDispatcherClient.connect(
        fabric_name, policy=OffloadPolicy(max_batch=max_batch))
    rec = {"k": [], "due": [], "sent": [], "done": [], "tokens": [],
           "errors": []}
    try:
        ready.put(idx)
        # a client stamps its liveness word only once its receiver thread
        # runs, from its first request on, and the server reaps one whose
        # stamp is 2 s old: stamp it while waiting to send
        beat = client.transport.heartbeat
        waited = time.perf_counter()
        while not go.wait(0.1):
            beat()
            if time.perf_counter() - waited > 1800:
                raise TimeoutError("the server never opened the window")
        t0 = t0_value.value
        t_end = t0 + seconds
        pending: queue.Queue = queue.Queue()

        def send(k: int, due: float) -> None:
            sent = time.perf_counter()
            jid = client.request("generate", prompts[k], mode="pipelined")
            pending.put((k, jid, due, sent))

        def wait_replies() -> None:
            while True:
                item = pending.get()
                if item is None:
                    return
                k, jid, due, sent = item
                left = t_end + GRACE_S - time.perf_counter()
                try:
                    # the client's own completion wait (hybrid polling),
                    # without query()'s liveness layer: that resubmits a
                    # request after 2 s of silence and may reconnect,
                    # closing the transport under a concurrent send
                    out = client.queries.query(jid, max(left, 0.01))
                    if isinstance(out, Exception):
                        raise out
                    out = np.asarray(out)
                    done = time.perf_counter()
                except Exception as e:         # counted, never fatal
                    out, done = None, math.nan
                    rec["errors"].append(f"{type(e).__name__}: {e}")
                rec["k"].append(k)
                rec["due"].append(due)
                rec["sent"].append(sent)
                rec["done"].append(done)
                rec["tokens"].append(out)

        sleep_until(t0, beat)
        cpu0 = time.process_time()
        waiter = threading.Thread(target=wait_replies, daemon=True)
        waiter.start()
        for k, off in plan:
            sleep_until(t0 + off, beat)
            send(k, t0 + off)
        sleep_until(t_end)
        cpu1 = time.process_time()
        pending.put(None)
        waiter.join(GRACE_S + 30)
        if waiter.is_alive():
            raise TimeoutError("replies still outstanding past the grace")
    finally:
        client.close()
    results.put({
        "client": idx, **rec, "cpu_s": cpu1 - cpu0,
        "retries": client.retries, "dup_replies": client.dup_replies,
        "lost_replies": client.lost_replies,
        "reconnects": client.reconnects,
        "jax_backend": xla_bridge.backends_are_initialized(),
    })


def sleep_until(t: float, beat=None) -> None:
    """Sleep until ``t``, stamping ``beat()`` at least every 0.1 s."""
    while True:
        delay = t - time.perf_counter()
        if delay <= 0:
            return
        time.sleep(min(delay, 0.1))
        if beat is not None:
            beat()
