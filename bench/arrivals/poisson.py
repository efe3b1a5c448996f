"""Poisson arrivals: ``n`` instants uniform over the window given their
count, one fixed pattern from ``base`` turned round the window by an
offset that the seed's ``rng`` draws."""


def instants(base, rng, n: int, seconds: float, a: dict):
    return (base.uniform(0.0, seconds, n) + rng.uniform(0.0, seconds)) \
        % seconds
