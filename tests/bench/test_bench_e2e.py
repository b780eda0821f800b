"""The harness path end to end on the CPU at a smoke size: spawned
clients, the fabric, the dispatcher, the served programs, the reference
check and the metric readers.  It enters through the test-only
arguments of ``run_cell``; the chip command itself refuses a CPU."""
import json
import os
import subprocess
import sys

import pytest

import _bench_path
from bench import gen
from bench.harness import run_cell

SMALL = dict(num_layers=2, d_model=64, num_heads=4, num_kv_heads=2,
             head_dim=16, d_ff=128, vocab_size=128)


def _config():
    with open(os.path.join(_bench_path.ROOT, "bench", "configs",
                           "granite-moe-1b-a400m.json")) as f:
        conf = json.load(f)
    conf["changes"] = dict(SMALL, dtype="float32", param_dtype="float32",
                           fsdp=False, remat=False, num_experts=4,
                           num_experts_per_token=2)
    conf["arch"].update(SMALL, num_experts=4, experts_per_token=2)
    conf["dtype"] = "float32"
    conf["serve"]["max_batch"] = 4
    return conf


def _mix():
    # two clients, not the mix's eight: polling clients take a core each,
    # and the suite runs beside other tests
    return dict(gen.load_mix("classify-open"), clients=2, rate_per_s=40,
                check_requests=24)


@pytest.mark.parametrize("trace", [0, 1])
def test_sound_run_is_correct_and_reports_its_metrics(trace):
    line = run_cell("moe-classify-open", 2 ** 33 + 1, 2.0, bool(trace),
                    platform="cpu", config=_config(), mix=_mix())
    assert line["correct"], line["checks"]
    assert line["attempted"] == 80 and line["failed"] == 0
    assert line["device"]["platform"] == "cpu"
    assert list(line)[-1] == "checks"
    load = line["load"]
    assert load["gen_late_p95_ms"] >= 0 and load["mean_batch"] >= 1
    if trace:
        # the CPU has no device plane: device metrics are left out
        assert set(line["metrics"]) == {"gen_late_ms.classify",
                                        "tail_p95_ms.classify",
                                        "ipc_us_per_req",
                                        "dispatch_wait_ms.open"}
        assert line["metrics"]["tail_p95_ms.classify"]["value"] > 0
    else:
        # the classify cell's 95th percentile is read per layer only
        assert set(line["metrics"]) == {"latency_p50_ms",
                                        "host_cpu_ms_per_req", "setup_s"}
        assert line["metrics"]["latency_p50_ms"]["value"] > 0


def test_a_token_altered_where_it_is_made_fails_correct():
    line = run_cell("moe-classify-open", 2 ** 33 + 2, 2.0, False,
                    platform="cpu", config=_config(), mix=_mix(),
                    fault="token")
    gap = line["checks"]["mean_logit_gap"]
    assert gap["value"] > gap["limit"]
    assert not line["correct"]


def test_the_chip_command_refuses_a_cpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "moe-classify-open",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=_bench_path.ROOT, env=env, capture_output=True, text=True,
        timeout=300)
    assert proc.returncode != 0
    assert '"correct"' not in proc.stdout
