"""The traffic generator: seeded schedules and exact percentiles."""
import math

import numpy as np
import pytest

import _bench_path  # noqa: F401
from bench import gen

BIG_SEED = 2 ** 33 + 12345


def test_percentile_is_nearest_rank_over_due_times():
    due = np.array([0.0, 0.1, 0.2, 0.3])
    done = due + np.array([0.010, 0.030, 0.020, 0.040])
    lat = (done - due) * 1e3
    assert gen.percentile(lat, 50) == pytest.approx(20.0)
    assert gen.percentile(lat, 95) == pytest.approx(40.0)
    assert gen.percentile(list(range(1, 101)), 95) == 95
    assert gen.percentile([5.0, math.inf], 95) == math.inf


def test_open_schedule_same_work_for_every_seed():
    mix = gen.load_mix("classify-open")
    a = gen.open_schedule(mix, 1, 10.0, rate=50)
    b = gen.open_schedule(mix, BIG_SEED, 10.0, rate=50)
    for sched in (a, b):
        assert [len(c) for c in sched] == gen.split_counts(
            500, gen.zipf_shares(8, 1.1))
        offs = sorted(t for c in sched for _, t in c)
        assert offs[0] == 0.0 and offs[-1] < 10.0
    gaps = [np.sort(np.diff(sorted(t for c in s for _, t in c)))
            for s in (a, b)]
    assert not np.allclose(gaps[0], np.diff(sorted(
        t for c in a for _, t in c)))          # the order is shuffled
    assert a != b
    assert gen.open_schedule(mix, 7, 10.0, rate=50) == \
        gen.open_schedule(mix, 7, 10.0, rate=50)


def test_counts_and_prompts():
    # dense-chat-open's 3.2 req/s over 51 s: 163 requests, Zipf(1.1)
    assert gen.split_counts(163, gen.zipf_shares(8, 1.1)) == \
        [65, 30, 19, 14, 11, 9, 8, 7]
    p = gen.prompt(BIG_SEED, 3, 9, 256, 49155)
    assert p.dtype == np.int32 and p.shape == (256,)
    assert (p == gen.prompt(BIG_SEED, 3, 9, 256, 49155)).all()
    assert not (p == gen.prompt(BIG_SEED, 3, 10, 256, 49155)).all()
