"""chip_smoke.py's phases on the CPU at a tiny size.

The phases check their own answers against NumPy and raise on any
difference; here they run in the Pallas interpreter, which the script's own
device check would refuse, so the tests call the phase functions directly.
"""

import json
import os
import shutil
import subprocess
import sys
import textwrap

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import chip_smoke  # noqa: E402


def test_phase_store_and_serve_matches_numpy(tmp_path):
    info = chip_smoke.phase_store_and_serve(
        str(tmp_path), 0, shards=2, rows_per_shard=8192, rows_per_group=2048)
    assert info["rows"] == 16384
    assert info["kernel_calls"] >= 3 * 8          # queries a, b, d x groups
    assert info["sketch_pruned"] > 0
    assert info["mosaic"] is False                # CPU: the interpreter


def test_phase_train_finite_losses(tmp_path):
    losses = chip_smoke.phase_train(str(tmp_path), 0, layers=2, batch=2,
                                    seq=32, steps=5, smoke=True)
    assert len(losses) == 5 and all(np.isfinite(losses))


def test_phase_sharded_ingest_on_four_cpu_devices(tmp_path):
    code = textwrap.dedent(f"""
        import os, sys
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
        sys.path.insert(0, {REPO!r})
        import chip_smoke
        out = chip_smoke.phase_sharded_ingest({str(tmp_path)!r}, 0, layers=2,
                                              seq=32, steps=3, smoke=True)
        assert len(out["losses"]) == 3 and out["rel"] <= 1e-3, out
        print("OK")
    """)
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, cwd=REPO, timeout=300,
                       env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert r.returncode == 0 and "OK" in r.stdout, (r.stdout[-2000:],
                                                    r.stderr[-3000:])


def _no_result(r) -> bool:
    lines = r.stdout.strip().splitlines()
    if not lines:
        return True
    try:
        return not json.loads(lines[-1]).get("ok")
    except (ValueError, AttributeError):
        return True


def test_refuses_to_run_without_a_tpu():
    r = subprocess.run([sys.executable, "chip_smoke.py"], capture_output=True,
                       text=True, cwd=REPO, timeout=300,
                       env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert r.returncode != 0 and _no_result(r)
    assert "'cpu'" in r.stderr


def test_fails_outside_the_repo(tmp_path):
    shutil.copy(os.path.join(REPO, "chip_smoke.py"), tmp_path)
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    r = subprocess.run([sys.executable, "chip_smoke.py"], capture_output=True,
                       text=True, cwd=tmp_path, timeout=300, env=env)
    assert r.returncode != 0 and _no_result(r)


@pytest.mark.parametrize("placed", [True, False], ids=["env", "checkout"])
def test_compile_cache_placement(tmp_path, placed):
    """Entry points cache compiles in JAX_COMPILATION_CACHE_DIR where it is
    set, and in <checkout>/.jax_cache otherwise."""
    want = str(tmp_path / "cache") if placed else os.path.join(REPO,
                                                                ".jax_cache")
    code = textwrap.dedent(f"""
        import os, sys
        sys.path.insert(0, {os.path.join(REPO, "src")!r})
        import jax, jax.numpy as jnp
        from repro.launch.cache import enable_compile_cache
        where = enable_compile_cache()
        assert where == {want!r}, where
        before = set(os.listdir(where)) if os.path.isdir(where) else set()
        jax.jit(lambda x: jnp.sin(x) * {os.getpid()})(jnp.ones(7)).block_until_ready()
        assert set(os.listdir(where)) - before, "nothing cached"
        print("OK")
    """)
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    env.update(JAX_PLATFORMS="cpu",
               JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0")
    if placed:
        env["JAX_COMPILATION_CACHE_DIR"] = want
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, cwd=tmp_path, timeout=300, env=env)
    assert r.returncode == 0 and "OK" in r.stdout, (r.stdout[-2000:],
                                                    r.stderr[-3000:])
