"""YCSB's UniformLongGenerator: every item number equally likely."""


def items(rng, n_items: int, size: int, k: dict):
    return rng.integers(0, n_items, size)
