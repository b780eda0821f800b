"""Traffic: one general generator driven by a mix's data file.

A mix (``bench/traffic/<name>.json``) states the prompt length, the new
tokens per request, the client processes and how skewed their shares are
(Zipf exponent), and an open-loop rate (``rate_per_s``) with the shape
of its arrivals.  Everything drawn comes from ``--seed``:

- the arrival gaps are the same set for every seed (the
  quantiles of the gap distribution, scaled to fill the window exactly at
  the mix's rate), put in another order by the seed; so seeds change
  which request comes when, never how much work a run holds.  The gaps
  are exponential (Poisson arrivals) unless the mix names other
  ``arrivals``: ``"gamma"`` with its coefficient of variation ``cv``
  gives bursts at the same mean rate;
- each request is assigned to a client so that the clients' counts follow
  the Zipf shares, in a seeded order;
- prompt ``k`` of client ``c`` is drawn from ``(seed, c, k)`` alone, so
  the server side can rebuild any prompt for the reference.
"""
from __future__ import annotations

import json
import math
from pathlib import Path

import numpy as np

TRAFFIC_DIR = Path(__file__).resolve().parent / "traffic"
REQUIRED = ("prompt_len", "new_tokens", "clients", "zipf_s", "rate_per_s",
            "check_requests", "trace_seconds")


def load_mix(name: str) -> dict:
    mix = json.loads((TRAFFIC_DIR / f"{name}.json").read_text())
    missing = [k for k in REQUIRED if k not in mix]
    if missing:
        raise ValueError(f"traffic {name}: missing {missing}")
    return mix


def zipf_shares(n: int, s: float) -> np.ndarray:
    w = 1.0 / np.arange(1, n + 1, dtype=np.float64) ** s
    return w / w.sum()


def split_counts(total: int, shares: np.ndarray) -> list:
    """Whole counts summing to ``total`` in proportion to ``shares``
    (largest remainder)."""
    raw = shares * total
    counts = np.floor(raw).astype(int)
    order = np.argsort(-(raw - counts), kind="stable")
    counts[order[: total - counts.sum()]] += 1
    return [int(c) for c in counts]


def arrival_gaps(mix: dict, n: int) -> np.ndarray:
    """``n`` gaps at the ``n`` mid-quantiles of the mix's gap
    distribution, in rising order: the exponential (Poisson arrivals)
    where ``arrivals`` is absent, or ``"gamma"`` with coefficient of
    variation ``cv`` (shape ``1 / cv**2``: above 1, bursts)."""
    q = (np.arange(n) + 0.5) / n
    kind = mix.get("arrivals", "exponential")
    if kind == "exponential":
        return -np.log1p(-q)
    if kind == "gamma":
        from scipy.special import gammaincinv
        return gammaincinv(1.0 / mix["cv"] ** 2, q)
    raise ValueError(f"unknown arrivals {kind!r}")


def open_schedule(mix: dict, seed: int, seconds: float,
                  rate: float | None = None) -> list:
    """Per client, the list of ``(k, due offset in s)``."""
    rate = mix["rate_per_s"] if rate is None else rate
    n = max(1, int(round(rate * seconds)))
    rng = np.random.default_rng([seed, 1])
    gaps = arrival_gaps(mix, n)
    gaps = rng.permutation(gaps) * (seconds / gaps.sum())
    due = np.concatenate([[0.0], np.cumsum(gaps)[:-1]])
    counts = split_counts(n, zipf_shares(mix["clients"], mix["zipf_s"]))
    owner = rng.permutation(np.repeat(np.arange(mix["clients"]), counts))
    per_client: list = [[] for _ in range(mix["clients"])]
    for t, c in zip(due, owner):
        per_client[c].append((len(per_client[c]), float(t)))
    return per_client


def prompt(seed: int, client: int, k: int, length: int,
           vocab: int) -> np.ndarray:
    rng = np.random.default_rng([seed, 2, client, k])
    return rng.integers(0, vocab, length, dtype=np.int32)


def percentile(values, p: float) -> float:
    """Nearest-rank percentile (exact: one of the values, no
    interpolation); ``inf`` entries stand for requests that never
    completed and count as missing every limit."""
    v = np.sort(np.asarray(values, np.float64))
    if not len(v):
        raise ValueError("no values")
    return float(v[max(0, math.ceil(p / 100.0 * len(v)) - 1)])
