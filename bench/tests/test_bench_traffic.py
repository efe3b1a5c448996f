"""The traffic generator and the table generator: the same work for every
seed, bit for bit the same for one seed, YCSB's key draws and the arrival
kinds."""

import numpy as np

from bench import generate, spec, traffic

BIG = 2**31 + 123_457


SCRAMBLED = spec.plugin("draws", "scrambled_zipf")
# a second, rarer template, to see the shares kept and spread
RANGE = {"name": "range", "class": "scan", "share": 0.05, "columns": ["key"],
         "where": [["I1", ">=", "$a"]], "grid": {"a": [0.5, 1.0]}}


def mix(name):
    return traffic.load_mix(spec.traffic_file(name))


def test_open_loop_has_the_same_work_for_every_seed():
    m = mix("lookup-zipf")
    assert [t["name"] for t in m["templates"]] == ["lookup"]   # YCSB-C
    m = {**m, "templates": m["templates"] + [RANGE]}
    keys = np.arange(10_000, dtype=np.int64) * 7
    plans = [traffic.plan(m, s, 10.0, lambda c: keys) for s in (1, BIG)]
    for p in plans:
        n = int(round(m["rate_per_s"] * 10.0))
        assert len(p["specs"]) == len(p["due"]) == n
        assert sorted(p["due"]) == p["due"] and 0 <= p["due"][0]
        assert p["due"][-1] < 10.0
        classes = [s["class"] for s in p["specs"]]
        assert classes.count("scan") == int(0.05 / 1.05 * n)
        # the rarer template's requests are spread evenly over the window
        at = [i for i, c in enumerate(classes) if c == "scan"]
        assert max(b - a for a, b in zip(at, at[1:])) <= -(-n // len(at))
    # the same arrivals and keys for every seed, in another order
    assert plans[0]["due"] != plans[1]["due"]
    gaps = [np.sort(np.diff(p["due"] + [p["due"][0] + 10.0]))
            for p in plans]
    assert np.allclose(gaps[0], gaps[1])
    items = [sorted(s["where"][0][2] for s in p["specs"]
                    if s["class"] == "lookup") for p in plans]
    assert items[0] == items[1]
    again = traffic.plan(m, BIG, 10.0, lambda c: keys)
    assert again == plans[1]


def test_closed_loop_cycles_every_grid_point():
    m = mix("quality-scan")
    p = traffic.plan(m, BIG, 30.0)
    assert len(p["specs"]) == 16 and p["connections"] == 2
    for seq in p["sequences"]:
        assert sorted(seq) == list(range(16))
    values = {tuple(w[2] for w in s["where"]) for s in p["specs"]}
    assert len(values) == 16


def test_fnv64_matches_ycsb():
    # Utils.fnvhash64 of 0 and 1 (FNV-1 64 over 8 octets, Math.abs)
    def ref(v):
        h = 0xCBF29CE484222325
        for _ in range(8):
            h ^= v & 0xFF
            h = (h * 1099511628211) & ((1 << 64) - 1)
            v >>= 8
        h = h - (1 << 64) if h >= 1 << 63 else h
        return abs(h)
    vals = np.array([0, 1, 12345, 10**10 - 1], np.int64)
    assert SCRAMBLED.fnv64(vals).tolist() == [ref(int(v)) for v in vals]


def test_scrambled_zipf_is_skewed_and_spread():
    rng = np.random.default_rng(0)
    items = SCRAMBLED.items(rng, 1_000_000, 200_000, {"theta": 0.99})
    assert items.min() >= 0 and items.max() < 1_000_000
    _, counts = np.unique(items, return_counts=True)
    top = np.sort(counts)[::-1]
    # item 0 of the Zipfian is drawn with probability 1 / zeta(10**10)
    assert abs(top[0] / 200_000 - 1 / 26.469) < 0.005
    # the hot items are scattered, not the smallest numbers
    hot = np.unique(items)[np.argsort(counts)[::-1][:10]]
    assert hot.max() > 100_000


def test_generated_columns_repeat_for_a_seed_and_differ_across_seeds():
    cfg = generate.load_config(spec.config_file("laion-meta"))
    cfg = {**cfg, "rows_per_shard": 4096}
    a = generate.generate_shard(cfg, BIG, 1)
    b = generate.generate_shard(cfg, BIG, 1)
    c = generate.generate_shard(cfg, BIG + 1, 1)
    for name in a:
        assert (a[name] == b[name]) if isinstance(a[name], list) \
            else np.array_equal(a[name], b[name])
    assert not np.array_equal(a["aesthetic"], c["aesthetic"])
    assert a["URL"][0].startswith(b"https://")
    assert a["SAMPLE_ID"][0] == 100_000_000 + 4096
    key = generate.generate_table(
        generate.load_config(spec.config_file("criteo-features"))
        | {"rows_per_shard": 4096}, BIG, ["key"])["key"]
    assert len(np.unique(key)) == len(key)


def test_uniform_draw_covers_the_key_space_evenly():
    rng = np.random.default_rng(0)
    items = spec.plugin("draws", "uniform").items(rng, 1000, 200_000, {})
    assert items.min() == 0 and items.max() == 999
    counts = np.bincount(items, minlength=1000)
    assert counts.max() < 2 * counts.mean()


def test_on_off_arrivals_come_only_in_the_on_stretches():
    a = {"kind": "on_off", "period_s": 2.0, "on_share": 0.5}
    m = {**mix("lookup-zipf"), "arrivals": a}
    keys = np.arange(1000, dtype=np.int64)
    plans = [traffic.plan(m, s, 20.0, lambda c: keys) for s in (3, BIG)]
    for p in plans:
        due = np.asarray(p["due"])
        assert len(due) == 240 and np.all(np.diff(due) >= 0)
        assert due.min() >= 0 and due.max() < 20.0
    # one pattern turned round the window: the same gaps for every seed
    gaps = [np.sort(np.diff(p["due"] + [p["due"][0] + 20.0])) for p in plans]
    assert np.allclose(gaps[0], gaps[1])
    # unturned, every instant lies in the first half of its period
    base = np.random.default_rng(0)
    zero = type("NoTurn", (), {"uniform": lambda self, lo, hi: 0.0})()
    t = spec.plugin("arrivals", "on_off").instants(base, zero, 5000, 20.0, a)
    assert np.all(t % 2.0 < 1.0)
    assert abs(np.mean(t < 10.0) - 0.5) < 0.05
