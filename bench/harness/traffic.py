"""The one traffic generator: a mix's data file plus a seed -> arrivals and
requests.

A mix file under ``bench/traffic`` holds

    arrivals        "open" (independent users: requests fall due at a fixed
                    rate whatever the system does) or "closed" (callers that
                    each wait for their answer: ``outstanding`` requests in
                    flight, the next one sent as one finishes)
    rate_per_s      open: the offered load
    outstanding     closed: requests in flight
    max_rate_per_s  closed: a rate above what the system completes, which
                    sizes the pool of requests (the pool repeats if used up)
    method, schedule, m, n_int, adaptive, tol, m_max, n_masks
                    the explanation every request asks for
    min_len, max_len  prompt lengths (token models)

The inputs themselves (images, prompts) come from the configuration's own
``make_inputs``, drawn from the seed. Open-loop gaps are the quantiles of
an exponential distribution at ``rate_per_s`` in one fixed shuffled order:
every seed offers the same arrival times (queueing tails swung with the
order by up to twofold between seeds), and the seed draws the weights and
the inputs.
"""
from __future__ import annotations

import math

import numpy as np

ENGINE_KEYS = ("method", "schedule", "m", "n_int", "adaptive", "tol", "m_max", "n_masks")


def rngs(seed: int):
    """(numpy Generator, 31-bit int for a jax key) from any whole-number seed."""
    ss = np.random.SeedSequence(int(seed))
    key = int(ss.generate_state(1)[0]) & 0x7FFFFFFF
    return np.random.default_rng(ss), key


ARRIVAL_ORDER_SEED = 20231017


def arrivals(traffic: dict, seconds: float) -> np.ndarray:
    """Due times (s from the window's start) of an open-loop mix."""
    rate = float(traffic["rate_per_s"])
    n = int(math.ceil(rate * seconds * 1.25)) + 8
    gaps = -np.log1p(-(np.arange(n) + 0.5) / n) / rate
    np.random.default_rng(ARRIVAL_ORDER_SEED).shuffle(gaps)
    return np.cumsum(gaps)


def pool_size(traffic: dict, seconds: float) -> int:
    if traffic["arrivals"] == "open":
        return int(math.ceil(float(traffic["rate_per_s"]) * seconds * 1.25)) + 8
    return int(traffic["outstanding"]) + int(math.ceil(float(traffic["max_rate_per_s"]) * seconds))


def engine_kwargs(traffic: dict) -> dict:
    kw = {k: traffic[k] for k in ENGINE_KEYS if k in traffic}
    kw.setdefault("adaptive", False)
    return kw
