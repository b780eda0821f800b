"""The per-layer reader that needs a device trace, on a synthetic run:
two batches inside the traced window, one straddling its end."""
import json
import os

import pytest

import _bench_path
from bench import xtrace
from bench.harness import Run, load_benchmark, metric_reader
from bench.models import transformer as tf

MS = 1e6                                     # ns


def _run():
    with open(os.path.join(_bench_path.ROOT, "bench", "configs",
                           "granite-8b-18of36.json")) as f:
        arch = tf.Arch.from_dict(json.load(f)["arch"])
    mix = {"prompt_len": 512, "new_tokens": 3}
    run = Run("dense-chat-open", 1, 10.0, 1, arch, tf, mix)
    run.trace_lo, run.trace_hi = 0.0, 1000 * MS
    # batch of 16: prefill 400 ms, two decodes of 20 ms; batch of 4 the
    # same shape later; a third batch ends after the window and is left out
    mods = [("jit_prefill(1)", 10 * MS, 410 * MS),
            ("jit_decode(2)", 420 * MS, 440 * MS),
            ("jit_decode(2)", 450 * MS, 470 * MS),
            ("jit_prefill(1)", 500 * MS, 900 * MS),
            ("jit_decode(2)", 910 * MS, 930 * MS),
            ("jit_decode(2)", 940 * MS, 960 * MS),
            ("jit_prefill(1)", 970 * MS, 1100 * MS)]
    run.dtrace = xtrace.DeviceTrace(ops={"d": mods}, modules={"d": mods})
    run.batches = [(5 * MS, 480 * MS, 16, 512), (495 * MS, 965 * MS, 4, 512),
                   (965 * MS, 1200 * MS, 16, 512)]
    run.t0, run.t_end = 0.0, 10.0
    run.requests = [{"due": 0.0, "sent": 0.0, "done": 1.0}] * 20
    return run


def test_batch_device_time_counts_whole_batches_only():
    run = _run()
    assert metric_reader("batch_dev_ms.open")(run) == pytest.approx(440.0)


def test_readers_exist_for_every_metric_and_say_nothing_without_data():
    run = _run()
    run.dtrace = None
    for m in load_benchmark()["per_layer"]:
        read = metric_reader(m["name"])
        if m["source"] in ("device_trace", "program_span"):
            assert read(run) is None, m["name"]


@pytest.mark.parametrize("name,same_as", [
    ("tail_p95_ms.classify", "latency_p95_ms"),
    ("gen_late_ms.classify", "gen_late_ms.open")])
def test_classify_readers_read_what_their_counterparts_read(name, same_as):
    run = _run()
    run.requests = [{"due": 0.01 * i, "sent": 0.01 * i + 0.001 * (i % 7),
                     "done": 0.01 * i + 0.05 + 0.002 * i} for i in range(40)]
    value = metric_reader(name)(run)
    assert value is not None and value > 0
    assert value == metric_reader(same_as)(run)
