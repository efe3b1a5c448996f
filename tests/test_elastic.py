"""Elastic rescale: checkpoint saved under one mesh restores onto another."""

import os
import subprocess
import sys
import textwrap

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_elastic_restore_roundtrip(tmp_path):
    code = textwrap.dedent(f"""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import NamedSharding
        import repro.configs as configs
        from repro.models import zoo
        from repro.models.base import spec_tree
        from repro.distributed import make_dist
        from repro.launch.mesh import make_mesh
        from repro.train.checkpoint import CheckpointManager
        from repro.train.elastic import elastic_restore, shardings_for

        cfg = configs.get_smoke("llama3_2_1b").scaled(compute_dtype="float32")
        m = zoo.build(cfg)
        mesh8 = make_mesh((2, 4), ("data", "model"))
        sh8 = shardings_for(m.decl, mesh8)
        params = jax.tree.map(lambda t, s: jax.device_put(t, s),
                              m.init(jax.random.PRNGKey(0)), sh8)

        mgr = CheckpointManager({str(tmp_path)!r}, async_save=False)
        mgr.save(5, params)

        # restore onto a *different* mesh (half the fleet)
        mesh4 = make_mesh((1, 4), ("data", "model"))
        restored, manifest = elastic_restore(mgr, params, m.decl, mesh4)
        assert manifest["step"] == 5
        for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(restored)):
            assert np.array_equal(np.asarray(a), np.asarray(b))
        # restored arrays carry the new mesh's shardings
        leaf = jax.tree.leaves(restored)[0]
        assert leaf.sharding.mesh.size == 4
        # and the restored params still train
        tok = jax.random.randint(jax.random.PRNGKey(1), (2, 17), 0, cfg.vocab)
        with mesh4:
            m4 = zoo.build(cfg, make_dist(mesh4))
            loss = jax.jit(m4.loss)(restored, {{"tokens": tok}})
        assert bool(jnp.isfinite(loss))
        print("OK")
    """)
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, cwd=REPO, timeout=560,
                       env={**os.environ, "PYTHONPATH": os.path.join(REPO, "src")})
    assert r.returncode == 0 and "OK" in r.stdout, (r.stdout[-1500:],
                                                    r.stderr[-2500:])
