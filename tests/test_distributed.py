"""Distributed substrate: sharding resolution, checkpointing (atomic /
async / elastic), gradient compression, collective parsing.

Multi-device behaviors run in subprocesses with
``XLA_FLAGS=--xla_force_host_platform_device_count=N`` (the main test
process keeps the real 1-CPU view, as production smoke tests must)."""
import json
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.distributed import checkpoint as ck
from repro.launch.costs import parse_collectives


def _run_subprocess(body: str, devices: int = 8):
    """Run python code with N host devices; assert success."""
    script = ("import os\n"
              f"os.environ['XLA_FLAGS']='--xla_force_host_platform_device_count={devices}'\n"
              + textwrap.dedent(body))
    env = dict(os.environ, PYTHONPATH="src")
    r = subprocess.run([sys.executable, "-c", script], capture_output=True,
                       text=True, env=env, cwd=os.path.dirname(
                           os.path.dirname(os.path.abspath(__file__))))
    assert r.returncode == 0, f"stdout:\n{r.stdout}\nstderr:\n{r.stderr[-3000:]}"
    return r.stdout


# ---------------------------------------------------------------------------
# Sharding resolution
# ---------------------------------------------------------------------------

def test_resolve_spec_divisibility_fallback():
    _run_subprocess("""
    import jax
    from jax.sharding import PartitionSpec as P
    from repro.distributed.sharding import resolve_spec
    mesh = jax.make_mesh((2, 4), ("data", "model"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)
    rules = {"vocab": ("model",), "heads": ("model",), "batch": (("data",),)}
    # divisible -> sharded
    assert resolve_spec(("vocab", None), (64, 7), rules, mesh) == P("model")
    # not divisible -> replicated
    assert resolve_spec(("vocab", None), (65, 7), rules, mesh) == P()
    # axis uniqueness: second dim wanting 'model' loses
    s = resolve_spec(("vocab", "heads"), (64, 8), rules, mesh)
    assert s == P("model")
    print("resolve ok")
    """)


def test_attention_plan_matrix():
    from repro.distributed.sharding import attention_plan
    assert attention_plan(32, 8, 128, 16) == "heads"   # llama3
    assert attention_plan(32, 32, 64, 16) == "kv"      # stablelm
    assert attention_plan(40, 8, 128, 16) == "head_dim"  # llama4
    assert attention_plan(6, 3, 7, 16) == "replicate"


def test_zero_opt_sharding_adds_data_axis():
    _run_subprocess("""
    import jax, jax.numpy as jnp
    from jax.sharding import PartitionSpec as P
    from repro.configs import get_config
    from repro.models import Model
    from repro.distributed.sharding import make_rules
    from repro.training.steps import opt_state_shardings
    from repro.training.optimizer import abstract_opt_state
    mesh = jax.make_mesh((2, 4), ("data", "model"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)
    cfg = get_config("tinyllama-1.1b").smoke()
    m = Model(cfg)
    o = abstract_opt_state(m.abstract_params())
    sh = opt_state_shardings(o, m.param_dims(), make_rules(cfg, mesh), mesh)
    specs = [s.spec for s in jax.tree.leaves(sh.master)]
    flat = [str(s) for s in specs]
    assert any("data" in s for s in flat), flat
    print("zero ok")
    """)


# ---------------------------------------------------------------------------
# Checkpointing
# ---------------------------------------------------------------------------

def _tree(seed=0):
    r = np.random.default_rng(seed)
    return {"a": jnp.asarray(r.normal(size=(4, 8)).astype(np.float32)),
            "nested": {"b": jnp.asarray(r.integers(0, 5, (3,)))}}


def test_checkpoint_roundtrip(tmp_path):
    t = _tree()
    ck.save(str(tmp_path), 7, t)
    restored, step = ck.restore(str(tmp_path), t)
    assert step == 7
    np.testing.assert_array_equal(np.asarray(t["a"]),
                                  np.asarray(restored["a"]))
    np.testing.assert_array_equal(np.asarray(t["nested"]["b"]),
                                  np.asarray(restored["nested"]["b"]))


def test_checkpoint_atomicity_ignores_tmp(tmp_path):
    t = _tree()
    ck.save(str(tmp_path), 1, t)
    # a crashed save leaves a .tmp dir: must be invisible to latest_step
    os.makedirs(tmp_path / "step_00000099.tmp")
    assert ck.latest_step(str(tmp_path)) == 1


def test_checkpoint_retention(tmp_path):
    t = _tree()
    for s in range(6):
        ck.save(str(tmp_path), s, t, keep=2)
    steps = sorted(d for d in os.listdir(tmp_path) if d.startswith("step_"))
    assert len(steps) == 2 and steps[-1] == "step_00000005"


def test_async_checkpointer(tmp_path):
    c = ck.AsyncCheckpointer(str(tmp_path), keep=2)
    t = _tree()
    c.save_async(3, t)
    c.wait()
    restored, step = ck.restore(str(tmp_path), t)
    assert step == 3
    np.testing.assert_array_equal(np.asarray(t["a"]), np.asarray(restored["a"]))


def test_elastic_restore_different_mesh(tmp_path):
    """Save under a (2,2) mesh sharding, restore under (4,1) — elastic."""
    _run_subprocess(f"""
    import jax, jax.numpy as jnp, numpy as np
    from jax.sharding import PartitionSpec as P, NamedSharding
    from repro.distributed import checkpoint as ck
    mesh1 = jax.make_mesh((2, 2), ("data", "model"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)
    x = jnp.arange(64, dtype=jnp.float32).reshape(8, 8)
    xs = jax.device_put(x, NamedSharding(mesh1, P("data", "model")))
    ck.save({str(tmp_path)!r}, 1, {{"w": xs}})
    mesh2 = jax.make_mesh((4, 1), ("data", "model"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)
    sh2 = {{"w": NamedSharding(mesh2, P("model", "data"))}}
    restored, _ = ck.restore({str(tmp_path)!r}, {{"w": x}}, shardings=sh2)
    np.testing.assert_array_equal(np.asarray(restored["w"]), np.asarray(x))
    assert restored["w"].sharding.spec == P("model", "data")
    print("elastic ok")
    """)


# ---------------------------------------------------------------------------
# Gradient compression
# ---------------------------------------------------------------------------

def test_int8_quantization_error_feedback():
    from repro.distributed.compression import compress_tree, init_error_buffer
    r = np.random.default_rng(0)
    g = {"w": jnp.asarray(r.normal(0, 1, (64, 64)).astype(np.float32))}
    e = init_error_buffer(g)
    q, s, e2 = compress_tree(g, e)
    deq = np.asarray(q["w"], np.float32) * float(s["w"])
    rel = np.abs(deq - np.asarray(g["w"])).max() / np.abs(np.asarray(g["w"])).max()
    assert rel < 0.02                      # int8 quantization error bound
    # error buffer carries exactly the residual
    np.testing.assert_allclose(np.asarray(e2["w"]),
                               np.asarray(g["w"]) - deq, rtol=1e-5, atol=1e-6)


def test_compressed_psum_multidevice():
    _run_subprocess("""
    import jax, jax.numpy as jnp, numpy as np
    from repro.distributed.compression import compressed_psum, init_error_buffer
    mesh = jax.make_mesh((4,), ("pod",),
                         axis_types=(jax.sharding.AxisType.Auto,) * 1)
    g = {"w": jnp.ones((8, 8), jnp.float32) * 2.0}
    e = init_error_buffer(g)
    with mesh:
        out, e2 = compressed_psum(g, e, mesh, axis="pod")
    np.testing.assert_allclose(np.asarray(out["w"]), 2.0, rtol=1e-2)
    print("psum ok")
    """)


def test_compression_convergence():
    """SGD on a quadratic with compressed grads converges (error feedback)."""
    from repro.distributed.compression import compress_tree, init_error_buffer
    r = np.random.default_rng(0)
    w = jnp.asarray(r.normal(0, 1, (16,)).astype(np.float32))
    target = jnp.asarray(r.normal(0, 1, (16,)).astype(np.float32))
    e = init_error_buffer({"w": w})
    for _ in range(300):
        g = {"w": w - target}
        q, s, e = compress_tree(g, e)
        deq = q["w"].astype(jnp.float32) * s["w"]
        w = w - 0.1 * deq
    assert float(jnp.abs(w - target).max()) < 1e-2


# ---------------------------------------------------------------------------
# HLO collective parser
# ---------------------------------------------------------------------------

def test_parse_collectives_known_hlo():
    hlo = """
  %x = f32[16,256]{1,0} parameter(0)
  %all-reduce.1 = f32[16,256]{1,0} all-reduce(%x), channel_id=1
  %fusion = bf16[16,256]{1,0} fusion(%all-reduce.1), kind=kLoop
  %ag = bf16[4,128]{1,0} all-gather(%fusion), dimensions={0}
  ROOT %t = (f32[2,2]{1,0}, f32[2,2]{1,0}) tuple(%x, %x)
"""
    out = parse_collectives(hlo, 4)
    assert out["counts"] == {"all-reduce": 1, "all-gather": 1}
    assert out["bytes_by_kind"]["all-reduce"] == 16 * 256 * 4
    assert out["bytes_by_kind"]["all-gather"] == 4 * 128 * 2
    # link model: AR 2x(n-1)/n, AG (n-1)/n
    expect = 2 * 16 * 256 * 4 * 0.75 + 4 * 128 * 2 * 0.75
    assert abs(out["link_bytes"] - expect) < 1e-6


def test_parse_collectives_ignores_operand_references():
    hlo = "  %f = f32[8]{0} fusion(%all-reduce.5), kind=kLoop\n"
    out = parse_collectives(hlo, 2)
    assert out["counts"] == {}
