"""Bursts: the window is cut into periods of ``period_s`` seconds, each on
for its first ``on_share`` and off for the rest. The ``n`` instants are
Poisson within the on stretches only, so the rate there is the mean rate
over ``on_share`` (0.5: twice the mean). One fixed pattern from ``base``,
turned round the window by an offset that the seed's ``rng`` draws."""

import numpy as np


def instants(base, rng, n: int, seconds: float, a: dict):
    period = float(a["period_s"])
    on = period * float(a["on_share"])
    t = base.uniform(0.0, seconds * on / period, n)   # instants in on time
    k = np.floor(t / on)
    return (k * period + (t - k * on) + rng.uniform(0.0, seconds)) % seconds
