"""Multi-device behaviour via subprocesses (the main pytest process must
keep seeing ONE device — jax locks the device count at first init)."""
import os
import subprocess
import sys
import textwrap

import pytest

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def run_py(code: str, devices: int = 8, timeout: int = 900):
    env = dict(os.environ)
    env["XLA_FLAGS"] = env.get("XLA_FLAGS", "") + \
        f" --xla_force_host_platform_device_count={devices}"
    env["PYTHONPATH"] = SRC
    r = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                       capture_output=True, text=True, timeout=timeout,
                       env=env)
    assert r.returncode == 0, r.stdout[-2000:] + r.stderr[-4000:]
    return r.stdout


def test_mesh_needs_enough_devices():
    """A mesh larger than the visible devices is an error, never a
    silently smaller placement."""
    import jax
    from repro.launch.mesh import make_test_mesh
    n = len(jax.devices())
    with pytest.raises(ValueError, match=f"needs {n + 1} devices"):
        make_test_mesh(1, n + 1)


def test_sharded_loss_matches_local():
    out = run_py("""
        import jax, jax.numpy as jnp
        from repro.configs import get_tiny_config
        from repro.models import lm
        from repro.parallel.sharding import use_sharding
        from repro.launch.mesh import make_test_mesh
        mesh = make_test_mesh(2, 4)
        for arch in ("qwen3-14b", "deepseek-v3-671b", "rwkv6-1.6b",
                     "recurrentgemma-2b"):
            cfg = get_tiny_config(arch)
            params = lm.init_params(jax.random.PRNGKey(0), cfg)
            B, S = 4, 64
            k1, k2 = jax.random.split(jax.random.PRNGKey(7))
            tokens = jax.random.randint(k1, (B, S), 0, cfg.vocab_size)
            labels = jax.random.randint(k2, (B, S), 0, cfg.vocab_size)
            batch = {"tokens": tokens, "labels": labels,
                     "mask": jnp.ones((B, S), jnp.float32)}
            l0, _ = jax.jit(lambda p, b: lm.loss_fn(p, cfg, b))(params, batch)
            with use_sharding(mesh):
                l1, _ = jax.jit(lambda p, b: lm.loss_fn(p, cfg, b))(
                    params, batch)
            assert abs(float(l0) - float(l1)) < 2e-2, (arch, l0, l1)
        print("OK")
    """)
    assert "OK" in out


def test_striped_store_write_keeps_placement():
    """``StripedStore.write`` goes through ``.at[].set`` — a scatter whose
    output sharding XLA may resolve to replicated.  The store re-pins the
    stripe after every write; this asserts the slab still carries the
    P("model") placement (and round-trips values) afterwards."""
    out = run_py("""
        import jax, jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.core.memory_server import StripedStore, stripe_slab_index
        from repro.parallel.sharding import use_sharding
        from repro.launch.mesh import make_test_mesh
        mesh = make_test_mesh(1, 4)
        with use_sharding(mesh):
            st = StripedStore(size=64)
            want = NamedSharding(mesh, P("model"))
            assert st.slab.sharding.is_equivalent_to(want, st.slab.ndim), \\
                st.slab.sharding
            addrs = jnp.array([0, 5, 17, 63])
            st.write(addrs, jnp.array([1., 2., 3., 4.]))
            # the write must not decay the stripe to replicated
            assert st.slab.sharding.is_equivalent_to(want, st.slab.ndim), \\
                st.slab.sharding
            assert jnp.array_equal(st.read(addrs),
                                   jnp.array([1., 2., 3., 4.]))
            # host rule and device placement agree: slab row of address a
            # is the stripe permutation, and row 0 stays row 0
            assert int(stripe_slab_index(0, st.n, st.size)) == 0
        print("OK")
    """, devices=4)
    assert "OK" in out


def test_lattice_allreduce_and_pipeline():
    out = run_py("""
        import jax, jax.numpy as jnp
        from repro.parallel.sharding import use_sharding
        from repro.parallel import lattice, pipeline
        mesh = jax.make_mesh((2, 4), ("pod", "data"))
        with use_sharding(mesh):
            x = jnp.arange(64, dtype=jnp.float32).reshape(8, 8)
            out = lattice.lattice_all_reduce(x, fast_axes=("data",),
                                             slow_axis="pod")
            assert jnp.allclose(out, x * 8)
        mesh2 = jax.make_mesh((4,), ("stage",))
        W = jax.random.normal(jax.random.PRNGKey(1), (4, 16, 16)) * 0.3
        x = jax.random.normal(jax.random.PRNGKey(2), (8, 16))
        stage_fn = lambda w, x: jnp.tanh(x @ w)
        seq = x
        for s in range(4):
            seq = stage_fn(W[s], seq)
        with use_sharding(mesh2):
            y = jax.jit(lambda W, x: pipeline.pipeline_apply(
                stage_fn, W, x, n_micro=4, axis="stage"))(W, x)
        assert jnp.abs(y - seq).max() < 1e-5
        print("OK")
    """)
    assert "OK" in out


def test_compressed_allreduce_error_feedback():
    out = run_py("""
        import jax, jax.numpy as jnp
        from repro.parallel.sharding import use_sharding
        from repro.optim import compress
        mesh = jax.make_mesh((4,), ("data",))
        with use_sharding(mesh):
            g = jax.random.normal(jax.random.PRNGKey(0), (3000,))
            err = jnp.zeros_like(g)
            # accumulated estimate over steps: error feedback keeps the
            # running mean unbiased-ish
            acc = jnp.zeros_like(g)
            for _ in range(8):
                red, err = jax.jit(lambda g, e: compress.compressed_all_reduce(
                    g, e, axis="data"))(g, err)
                acc = acc + red
            rel = float(jnp.abs(acc / 8 - g).max() / jnp.abs(g).max())
            assert rel < 0.02, rel
        print("OK")
    """)
    assert "OK" in out


def test_dryrun_small_mesh_cell():
    """A miniature dry-run: lower + compile a tiny arch on 8 devices,
    memory/cost/collective record extraction end to end."""
    out = run_py("""
        import jax
        from repro.configs import get_tiny_config
        from repro.configs.base import ShapeConfig
        from repro import steps as steps_mod
        from repro.parallel.sharding import use_sharding
        from repro.launch.mesh import make_test_mesh
        from repro.analysis import hlo
        cfg = get_tiny_config("qwen3-14b")
        shape = ShapeConfig("t", 128, 16, "train")
        mesh = make_test_mesh(2, 4)
        with use_sharding(mesh) as env:
            adam_cfg = steps_mod.adam_config_for(cfg)
            params, opt = steps_mod.make_state_structs(cfg, adam_cfg, mesh, env)
            batch = steps_mod.make_batch_struct(cfg, shape, mesh, env)
            step = steps_mod.make_train_step(cfg, adam_cfg)
            compiled = jax.jit(step, donate_argnums=(0, 1)).lower(
                params, opt, batch).compile()
            mem = compiled.memory_analysis()
            assert mem.temp_size_in_bytes > 0
            summ = hlo.collective_summary(compiled.as_text())
            assert summ["total_wire_bytes_per_device"] > 0
        print("OK")
    """)
    assert "OK" in out


def test_elastic_reshard_across_meshes():
    out = run_py("""
        import jax, jax.numpy as jnp
        from repro.configs import get_tiny_config
        from repro.models import lm
        from repro.optim import adam as adam_lib
        from repro.runtime import checkpoint as ckpt, elastic
        from repro.parallel.sharding import use_sharding
        from repro.launch.mesh import make_test_mesh
        import tempfile
        cfg = get_tiny_config("qwen3-14b")
        adam_cfg = adam_lib.AdamConfig()
        d = tempfile.mkdtemp()
        # save sharded on a (4,2) mesh
        mesh_a = make_test_mesh(4, 2)
        with use_sharding(mesh_a) as env:
            params = lm.init_params(jax.random.PRNGKey(0), cfg)
            opt = adam_lib.init(params, adam_cfg)
            ps, os_ = elastic.state_shardings(cfg, adam_cfg, env)
            params = jax.device_put(params, ps)
            opt = jax.device_put(opt, os_)
            ckpt.save(d, 5, {"params": params, "opt": opt})
        # restore onto a (2,4) mesh (elastic rescale)
        mesh_b = make_test_mesh(2, 4)
        step, p2, o2 = elastic.restore_elastic(d, cfg, adam_cfg, mesh_b)
        assert step == 5
        for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(p2)):
            assert jnp.array_equal(jax.device_get(a), jax.device_get(b))
        print("OK")
    """)
    assert "OK" in out
