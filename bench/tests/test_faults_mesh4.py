"""The mesh cell's harness at a size the CPU holds, on four virtual CPU
devices (in a child process: the device count is fixed when JAX starts).
Sound, ``DatasetJob(mode="device_steps")`` passes the reference within
the configuration's limits; with the timed path broken underneath
(``bench/faults_mesh4.py``), ``correct`` comes out false, each fault on
the number that exists for it, at least twice over its limit."""
import json
import os
import subprocess
import sys

import pytest

from bench import manifest

CELL = "graph500_s26_mesh4.device_steps"
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

#: the cell's configuration and limits at a scale-16 graph: 2^22 edges in
#: 2^19-edge steps (2^17 edges per device), where each fault reads at
#: least twice its limit
SCRIPT = r"""
import json, os, sys, tempfile, time
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
sys.path[:0] = [sys.argv[1], os.path.join(sys.argv[1], "src")]
from bench import cell, faults_mesh4, manifest
from bench.reference import kronecker_mesh4 as ref

bench = manifest.load()
w = manifest.workload(bench, sys.argv[2])
config = manifest.config(bench, w)
config["kronecker"].update(n=16, m=16, E=1 << 22)
config["job"]["shard_edges"] = 1 << 19
out = {}

def small_run():
    return cell.run(config, manifest.traffic(w), 2 ** 40 + 9, 2.0, False,
                    time.perf_counter(), {},
                    {"platform": "cpu", "kind": "cpu", "count": 4},
                    {"hbm_bytes_per_s": 819e9}, work_root=sys.argv[3])

out["sound"] = small_run()
for name, fault in sorted(faults_mesh4.FAULTS.items()):
    with fault():
        out[name] = small_run()

# the whole plan through DatasetJob.run, checked shard by shard
from repro.core.structure import KroneckerFit
from repro.datastream import DatasetJob, ShardedGraphDataset
kc = config["kronecker"]
d = tempfile.mkdtemp(dir=sys.argv[3])
DatasetJob(KroneckerFit(**kc), d, shard_edges=config["job"]["shard_edges"],
           seed=7, mode="device_steps").run()
ds = ShardedGraphDataset(d)
pl = ref.plan(ref.Kronecker(**{k: kc[k] for k in "abcdnmE"}),
              config["job"]["shard_edges"])
numbers = ref.compare(pl, {r.shard_id: {"src": ds.load_shard(r.shard_id).src,
                                        "dst": ds.load_shard(r.shard_id).dst}
                           for r in ds.manifest.shards})
numbers.update(shards_committed=len(pl.shards), shards_missing=0,
               shards_unreadable=0)
ok, checks = cell.verdict(numbers, config["limits"])
out["job"] = {"correct": ok, "checks": checks, "steps": len(pl.shards)}
print(json.dumps(out))
"""


@pytest.fixture(scope="module")
def runs(tmp_path_factory):
    work = str(tmp_path_factory.mktemp("mesh4"))
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run([sys.executable, "-c", SCRIPT, ROOT, CELL, work],
                       capture_output=True, text=True, env=env, timeout=600,
                       cwd=ROOT)
    assert r.returncode == 0, r.stderr[-3000:]
    return json.loads(r.stdout.strip().splitlines()[-1])


def _over(res, name, times=1.0):
    c = res["checks"][name]
    return c["value"] > times * c["limit"]


def test_sound_run_is_correct(runs):
    res = runs["sound"]
    assert res["correct"], res["checks"]
    assert res["attempted"] >= 1 and res["failed"] == 0
    assert set(res["metrics"]) == {"edges_per_s", "setup_s"}


def test_device_steps_job_passes_the_reference(runs):
    res = runs["job"]
    assert res["correct"], res["checks"]
    assert res["steps"] == 8


def test_the_parents_level_pairing(runs):
    res = runs["level_shift"]
    assert not res["correct"]
    assert res["checks"]["edges_outside_device"]["value"] == 0
    assert _over(res, "quadrant_z_level", 2)
    assert _over(res, "prefix_z", 2)


def test_the_dst_prefix_drawn_from_its_marginal(runs):
    res = runs["marginal_prefix"]
    assert not res["correct"]
    # the levels past the prefix keep their law: only the prefix sees it
    assert not _over(res, "quadrant_z_level")
    assert not _over(res, "pair_z_level")
    assert _over(res, "prefix_z", 2)


def test_two_devices_given_one_seed(runs):
    res = runs["shared_key"]
    assert not res["correct"]
    assert res["checks"]["repeated_runs"]["value"] > 0


@pytest.mark.parametrize("E", [None, (1 << 26) + 12345])
def test_reference_plan_is_the_programs(tmp_path, E):
    """The reference's plan of the cell's own configuration (and of one
    whose last step is cut short) matches the program's device-step
    records, step for step."""
    from repro.core.structure import KroneckerFit
    from repro.datastream import DatasetJob

    from bench.reference import kronecker_mesh4 as ref

    bench = manifest.load()
    config = manifest.config(bench, manifest.workload(bench, CELL))
    kc, se = dict(config["kronecker"]), config["job"]["shard_edges"]
    kc["E"] = E or kc["E"]
    pl = ref.plan(ref.Kronecker(**{x: kc[x] for x in "abcdnmE"}), se)
    job = DatasetJob(KroneckerFit(**kc), str(tmp_path / "ds"),
                     shard_edges=se, mode="device_steps")
    assert pl.shards == [r.n_edges for r in job._device_step_records()]
    assert pl.blocks(0) == [(d * se // 4, (d + 1) * se // 4)
                            for d in range(4)]
    if E is None:
        assert len(pl.shards) == 64
    else:
        last = pl.blocks(len(pl.shards) - 1)
        assert last[-1][1] == pl.shards[-1] == E % se


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_the_control(dtype):
    """The reference in the program's place passes the check in float32
    and fails it in bfloat16, on the pooled quadrant counts."""
    from bench import control

    bench = manifest.load()
    w = manifest.workload(bench, CELL)
    config = manifest.config(bench, w)
    config["kronecker"].update(n=16, m=16, E=1 << 22)
    config["job"]["shard_edges"] = 1 << 19
    r = control.readings(config, manifest.traffic(w), 2 ** 40 + 5, 4, dtype)
    c = r["checks"]["quadrant_z_pooled"]
    if dtype == "float32":
        assert r["correct"], r["checks"]
    else:
        assert not r["correct"] and c["value"] > c["limit"]
