"""The ROCKET core in isolation: calibrate the latency model, then drive the
async transfer engine and the tier-3 offload-copy kernel through the paper's
configuration space (mode × device × injection).

  PYTHONPATH=src python examples/offload_modes.py
"""
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import (AsyncTransferEngine, ExecutionMode, LatencyModel,
                        OffloadPolicy, calibrate)
from repro.core.policy import Device
from repro.kernels import ops, ref


def main():
    # 1. per-node calibration (the paper's deployment-time profiling script)
    model = calibrate(lambda b: jax.block_until_ready(jax.device_put(b)),
                      sizes_bytes=(1 << 18, 1 << 20, 1 << 22), repeats=5)
    print(f"calibrated: L = {model.l_fixed_us:.1f}us "
          f"+ {model.alpha_us_per_mb:.2f}us/MB "
          f"(implied bw {model.bandwidth_gbps():.0f} GB/s, "
          f"rel std {model.rel_std:.0%})")

    # 2. tier-1: engine modes over a 16MB message stream
    buf = np.ones((4 << 20,), np.float32)
    print("\ntier-1 engine (16MB x 8 transfers):")
    for mode in ExecutionMode:
        pol = OffloadPolicy(mode=mode, offload_threshold_bytes=1,
                            pipeline_depth=3)
        with AsyncTransferEngine(pol, latency=model) as eng:
            t0 = time.perf_counter()
            jobs = [eng.submit(buf) for _ in range(8)]
            for j in jobs:
                j.get()
            dt = (time.perf_counter() - t0) / 8 * 1e3
            s = eng.stats
            print(f"  {mode.value:10s} {dt:7.2f} ms/transfer  "
                  f"offloaded={s.offloaded} polls={s.polls}")

    # 3. the size threshold (offload control): small stays inline
    pol = OffloadPolicy(mode=ExecutionMode.ASYNC,
                        offload_threshold_bytes=1 << 20)
    with AsyncTransferEngine(pol, latency=model) as eng:
        eng.submit(np.ones(64, np.float32)).get()       # 256B  -> inline
        eng.submit(np.ones(1 << 20, np.float32)).get()  # 4MB   -> offload
        print(f"\nthreshold: inline={eng.stats.inline} "
              f"offloaded={eng.stats.offloaded} (paper Table III 'Data Size')")

    # 4. tier-3: the DSA-analogue Pallas kernel, in the Pallas interpreter
    # so the example runs on any backend
    x = jax.random.normal(jax.random.key(0), (1024, 256))
    print("\ntier-3 offload_copy kernel (mode x injection):")
    for mode in ("sync", "async", "pipelined"):
        for inject in (False, True):
            pol = OffloadPolicy(mode=ExecutionMode(mode),
                                offload_threshold_bytes=1,
                                cache_injection=inject)
            y, total = ops.offload_copy(x, scale=2.0, policy=pol,
                                        inject=inject, interpret=True)
            yr, tr = ref.offload_copy(x, scale=2.0, inject=inject)
            ok = bool(jnp.allclose(y, yr, atol=1e-5))
            extra = f" fused_sum={float(total):.1f}" if inject else ""
            print(f"  mode={mode:10s} inject={str(inject):5s} "
                  f"allclose={ok}{extra}")


if __name__ == "__main__":
    main()
