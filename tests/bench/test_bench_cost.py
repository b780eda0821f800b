"""Parameter counts of the configurations, against hand-worked numbers."""
import json
import os

import pytest

import _bench_path
from bench.models import transformer as tf


def _arch(name):
    with open(os.path.join(_bench_path.ROOT, "bench", "configs",
                           name + ".json")) as f:
        return tf.Arch.from_dict(json.load(f)["arch"])


def test_param_counts():
    dense = _arch("granite-8b-18of36")
    # 18 x (2*4096*4096 + 2*4096*1024 + 3*4096*14336 + 2*4096)
    # + 2 * 49152*4096 + 4096
    assert tf.param_count(dense) == 4_328_673_280
    assert tf.param_count(dense) * 2 / 1e9 == pytest.approx(8.66, abs=0.01)
    moe = _arch("granite-moe-1b-a400m")
    assert tf.param_count(moe) == 1_334_628_352       # the program's count
