"""Dry-run machinery: cost-probe accuracy vs fully-unrolled ground truth,
cell lowering on a small mesh, chunked-generation cell (all in subprocesses
with forced multi-device CPU)."""
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest


def _run(body: str, devices: int = 8, timeout: int = 900):
    script = ("import os\n"
              f"os.environ['XLA_FLAGS']='--xla_force_host_platform_device_count={devices}'\n"
              + textwrap.dedent(body))
    env = dict(os.environ, PYTHONPATH="src")
    r = subprocess.run([sys.executable, "-c", script], capture_output=True,
                       text=True, env=env, timeout=timeout,
                       cwd=os.path.dirname(os.path.dirname(
                           os.path.abspath(__file__))))
    assert r.returncode == 0, f"stdout:\n{r.stdout}\nstderr:\n{r.stderr[-4000:]}"
    return r.stdout


@pytest.mark.slow
def test_probe_extrapolation_matches_unrolled_truth():
    """probe(L=1,2)-extrapolated flops == fully-unrolled L=6 flops (±3%)."""
    _run("""
    import jax, dataclasses
    from repro.configs import get_config
    from repro.configs.base import ShapeSpec
    from repro.launch.costs import probe_costs, _lower_costs, _probe_cfg
    from repro.launch.mesh import make_local_mesh
    mesh = make_local_mesh(2, 4)
    cfg = get_config("tinyllama-1.1b").smoke().replace(n_layers=6)
    shape = ShapeSpec("t", 64, 8, "train")
    probe = probe_costs(cfg, shape, mesh)
    truth = _lower_costs(_probe_cfg(cfg), shape, mesh)
    rel = abs(probe.flops - truth["flops"]) / truth["flops"]
    print("flops rel err:", rel)
    assert rel < 0.03, (probe.flops, truth["flops"])
    relb = abs(probe.bytes - truth["bytes"]) / truth["bytes"]
    print("bytes rel err:", relb)
    # bf16-on-CPU convert chains add a superlinear bytes term the probe's
    # L∈{1,2} fit underestimates (absent on TPU; see launch/costs.py)
    assert relb < 0.30, (probe.bytes, truth["bytes"])
    # collectives: counts must match exactly
    assert probe.coll_counts == truth["coll"]["counts"], (
        probe.coll_counts, truth["coll"]["counts"])
    """)


@pytest.mark.slow
def test_chunk_extrapolated_probe_matches_direct():
    """The nc∈{2,4,8} quadratic fit reproduces a directly-probed nc=16 cell."""
    _run("""
    import jax
    from repro.configs import get_config
    from repro.configs.base import ShapeSpec
    from repro.launch import costs as C
    from repro.launch.mesh import make_local_mesh
    mesh = make_local_mesh(2, 4)
    cfg = get_config("rwkv6-7b").smoke()   # chunk=16 in smoke
    S = 16 * cfg.rwkv.chunk
    shape = ShapeSpec("t", S, 8, "prefill")
    direct = C._probe_costs_depth(cfg, shape, mesh)
    fitted = C._probe_costs_chunk_extrapolated(cfg, shape, mesh, None,
                                               (cfg.rwkv.chunk, 16))
    rel = abs(fitted.flops - direct.flops) / direct.flops
    print("chunk-fit flops rel err:", rel)
    assert rel < 0.05, (fitted.flops, direct.flops)
    """, timeout=1200)


@pytest.mark.parametrize("arch", ["llama3-8b", "qwen3-moe-30b-a3b"])
def test_cell_lowers_on_small_mesh(arch):
    """build_cell (smoke config) lowers+compiles on a 2x4 mesh with the
    same sharding rules as production."""
    _run(f"""
    import jax
    from repro.configs import get_config
    from repro.configs.base import ShapeSpec
    from repro.launch.mesh import make_local_mesh
    from repro.training.steps import build_cell
    mesh = make_local_mesh(2, 4)
    cfg = get_config({arch!r}).smoke()
    for shape in (ShapeSpec("t", 64, 8, "train"),
                  ShapeSpec("p", 64, 8, "prefill"),
                  ShapeSpec("d", 64, 8, "decode")):
        cell = build_cell(cfg, shape, mesh)
        with mesh:
            c = jax.jit(cell.fn, in_shardings=cell.in_shardings,
                        out_shardings=cell.out_shardings).lower(
                *cell.args).compile()
        assert c.cost_analysis()["flops"] > 0
        print(shape.kind, "ok")
    """)


def test_graphgen_cell_zero_collectives():
    """Chunked generation on the mesh: compiles and has NO collectives."""
    _run("""
    import jax
    from repro.core.distributed_gen import build_generation_cell
    from repro.launch.costs import parse_collectives
    from repro.launch.mesh import make_local_mesh
    mesh = make_local_mesh(2, 4)
    cell = build_generation_cell(mesh, "100b", edges_per_device=1 << 12)
    with mesh:
        c = jax.jit(cell.fn, in_shardings=cell.in_shardings,
                    out_shardings=cell.out_shardings).lower(*cell.args).compile()
    colls = parse_collectives(c.as_text(), 4)
    print("collectives:", colls["counts"])
    assert colls["payload_bytes"] == 0, colls
    """)


def test_distributed_generation_executes():
    """Actually run a tiny distributed generation step and check prefix
    disjointness across devices."""
    _run("""
    import jax, numpy as np, jax.numpy as jnp
    from repro.core.distributed_gen import device_generate
    from repro.launch.mesh import make_local_mesh
    mesh = make_local_mesh(2, 2)
    n = m = 8
    thetas = jnp.asarray(np.tile([0.45, 0.22, 0.2, 0.13], (8, 1)), jnp.float32)
    seeds = jnp.arange(4, dtype=jnp.int32)
    with mesh:
        src, dst = device_generate(thetas, seeds, n, m, 1024, mesh)
    src = np.asarray(src).reshape(4, -1)
    # the top 2 of the n src levels are the device index
    assert (src >> (n - 2) == np.arange(4)[:, None]).all()
    assert np.asarray(dst).max() < 2 ** m
    print("prefixes ok")
    """)
