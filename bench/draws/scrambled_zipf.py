"""YCSB's ScrambledZipfianGenerator: item numbers drawn by a Zipfian over
10**10 items whose zeta is fixed for the constant ``theta`` 0.99
(``ZipfianGenerator.nextValue``), then ``fnvhash64(item) % n_items``, so
the hot items lie scattered over the key space."""

import numpy as np

ITEMS = 10_000_000_000
ZETAN = 26.46902820178302
FNV_OFFSET = np.uint64(0xCBF29CE484222325)
FNV_PRIME = np.uint64(1099511628211)


def fnv64(v: np.ndarray) -> np.ndarray:
    """YCSB ``Utils.fnvhash64``: FNV-1 over the 8 low-to-high octets,
    then the absolute value as a signed 64-bit number."""
    v = np.asarray(v, np.int64).view(np.uint64).copy()
    h = np.full(v.shape, FNV_OFFSET, np.uint64)
    with np.errstate(over="ignore"):
        for _ in range(8):
            h ^= v & np.uint64(0xFF)
            h *= FNV_PRIME
            v >>= np.uint64(8)
    return np.abs(h.view(np.int64))


def items(rng, n_items: int, size: int, k: dict) -> np.ndarray:
    theta = float(k.get("theta", 0.99))
    if theta != 0.99:
        raise ValueError("the scrambled Zipfian's zeta is fixed for 0.99")
    zeta2 = 1.0 + 0.5 ** theta
    alpha = 1.0 / (1.0 - theta)
    eta = (1 - (2.0 / ITEMS) ** (1 - theta)) / (1 - zeta2 / ZETAN)
    u = rng.random(size)
    uz = u * ZETAN
    item = (ITEMS * (eta * u - eta + 1) ** alpha).astype(np.int64)
    item = np.where(uz < 1.0, 0, np.where(uz < 1.0 + 0.5 ** theta, 1, item))
    return fnv64(item) % n_items
