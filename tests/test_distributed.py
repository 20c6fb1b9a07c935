"""Multi-device distribution tests (8 fake host devices via subprocess, since
device count locks at first jax init)."""
import os
import subprocess
import sys
import textwrap

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# Every snippet builds its mesh through make_mesh(..., shrink=True), which
# shrinks the mesh instead of tripping the "mesh requires N devices"
# assertion when the subprocess ends up with fewer devices than requested.
_PRELUDE = """
    import jax
    from repro.launch.mesh import make_mesh
"""


def _run(code: str, devices: int = 8, timeout: int = 560) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["PYTHONPATH"] = os.path.join(ROOT, "src")
    env["JAX_PLATFORMS"] = "cpu"
    p = subprocess.run([sys.executable, "-c",
                        textwrap.dedent(_PRELUDE) + textwrap.dedent(code)],
                       capture_output=True, text=True, timeout=timeout,
                       env=env)
    assert p.returncode == 0, f"stdout={p.stdout}\nstderr={p.stderr}"
    return p.stdout


def test_moe_ep_matches_single_device():
    """Expert-parallel shard_map MoE == single-device MoE numerics."""
    out = _run("""
        import jax.numpy as jnp, dataclasses, numpy as np
        from repro.configs import get_reduced_config
        from repro.models import moe as moe_mod
        from repro.models.layers import Initializer
        mesh = make_mesh((2, 4), ("data", "model"), shrink=True)
        key = jax.random.PRNGKey(0)
        cfg = get_reduced_config("deepseek_v2_lite_16b").replace(
            param_dtype="float32", compute_dtype="float32")
        cfg = cfg.replace(moe=dataclasses.replace(cfg.moe, capacity_slack=8.0))
        p = moe_mod.init_moe(Initializer(cfg, key), "moe", cfg)
        leaves, td = jax.tree.flatten(p)
        ks = jax.random.split(key, len(leaves))
        p = jax.tree.unflatten(td, [l + jax.random.normal(k, l.shape) * 0.1
                                    for l, k in zip(leaves, ks)])
        x = jax.random.normal(jax.random.fold_in(key, 3), (8, 16, cfg.d_model))
        y1, _ = moe_mod.apply_moe(p, x, cfg, mesh=None)
        y2, _ = jax.jit(lambda p, x: moe_mod.apply_moe(p, x, cfg, mesh=mesh))(p, x)
        err = float(jnp.max(jnp.abs(y1 - y2)))
        assert err < 2e-3, err
        print("EP_OK", err)
    """)
    assert "EP_OK" in out


def test_sharded_train_step_runs_and_matches():
    """pjit'd train step on a (2,2,2) pod mesh == single-device step."""
    out = _run("""
        import jax.numpy as jnp, numpy as np
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.configs import get_reduced_config, SHAPES_BY_NAME
        from repro.models import steps, transformer as tf
        from repro.models.sharding import ShardingRules, tree_specs
        cfg = get_reduced_config("internlm2_20b").replace(
            param_dtype="float32", compute_dtype="float32", remat="none")
        mesh = make_mesh((2, 2, 2), ("pod", "data", "model"),
                                shrink=True)
        rules = ShardingRules(mesh)
        key = jax.random.PRNGKey(0)
        state = steps.init_train_state(cfg, key)
        batch = {"tokens": jax.random.randint(key, (8, 32), 0, cfg.vocab_size),
                 "labels": jax.random.randint(jax.random.fold_in(key, 1),
                                              (8, 32), 0, cfg.vocab_size)}
        _, m1 = steps.train_step(state, batch, cfg)
        with jax.set_mesh(mesh):
            fn = jax.jit(lambda s, b: steps.train_step(s, b, cfg, rules=rules,
                                                       mesh=mesh))
            _, m2 = fn(state, batch)
        d = abs(float(m1["loss"]) - float(m2["loss"]))
        assert d < 1e-3, (float(m1["loss"]), float(m2["loss"]))
        print("TRAIN_OK", d)
    """)
    assert "TRAIN_OK" in out


def test_dryrun_single_cell_on_small_mesh():
    """The dry-run machinery end-to-end on an 8-device (2,2,2) mesh."""
    out = _run("""
        from repro.launch import dryrun
        mesh = make_mesh((2, 2, 2), ("pod", "data", "model"),
                                shrink=True)
        from repro.configs import get_reduced_config
        cfg = get_reduced_config("internlm2_20b")
        res = dryrun.run_cell("internlm2_20b", "train_4k", mesh, True,
                              verbose=False, cfg_override=cfg.replace(
                                  num_layers=4))
        assert res["flops_per_dev"] > 0
        assert res["compute_term_s"] > 0
        print("DRYRUN_OK", res["dominant"])
    """, devices=8)
    assert "DRYRUN_OK" in out


def test_mesh_shrinks_to_fit_device_count():
    """shrink=True never requests more devices than exist (1-device run)."""
    out = _run("""
        mesh = make_mesh((2, 4), ("data", "model"), shrink=True)
        assert mesh.devices.size <= jax.device_count(), mesh.shape
        print("SHRINK_OK", dict(mesh.shape))
    """, devices=1)
    assert "SHRINK_OK" in out


def test_collective_bytes_parser():
    from repro.launch.dryrun import collective_bytes, wire_bytes
    hlo = """
      %all-reduce.1 = f32[8,128]{1,0} all-reduce(f32[8,128]{1,0} %x)
      %ag = bf16[16,256]{1,0} all-gather(bf16[2,256]{1,0} %y), dimensions={0}
      %cp = f32[4]{0} collective-permute(f32[4]{0} %z)
      %notacollective = f32[8]{0} add(f32[8]{0} %a, f32[8]{0} %b)
    """
    cb = collective_bytes(hlo)
    assert cb["all-reduce"] == 8 * 128 * 4
    assert cb["all-gather"] == 16 * 256 * 2
    assert cb["collective-permute"] == 16
    assert wire_bytes(cb) == 2 * 8 * 128 * 4 + 16 * 256 * 2 + 16
