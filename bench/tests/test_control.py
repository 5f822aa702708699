"""The control at a size the CPU holds: the reference in the program's
place passes the cell's check in float32 and fails it in bfloat16."""
import pytest

from bench import control, manifest


@pytest.fixture(scope="module")
def small():
    bench = manifest.load()
    w = manifest.workload(bench, "graph500_s26.chunks")
    config = manifest.config(bench, w)
    config["kronecker"].update(n=16, m=16, E=1 << 21)
    config["job"]["shard_edges"] = 1 << 17
    return config, manifest.traffic(w)


@pytest.mark.parametrize("seed", [1, 2 ** 40 + 5])
def test_reference_in_float32_passes(small, seed):
    r = control.readings(*small, seed=seed, n_shards=3, dtype="float32")
    assert r["correct"], r["checks"]


@pytest.mark.parametrize("seed", [1, 2 ** 40 + 5])
def test_reference_in_bfloat16_fails(small, seed):
    r = control.readings(*small, seed=seed, n_shards=3, dtype="bfloat16")
    assert not r["correct"]
    c = r["checks"]["quadrant_z_pooled"]
    assert c["value"] > c["limit"]
