"""A run of the harness, past its look for a chip, at a size the CPU
holds: sound, it comes out correct; with the timed path broken
underneath (``bench/faults.py``), ``correct`` comes out false, once for
each fault the cell can have.  (It has no state carried from step to
step and no exchange between chips, so those two faults do not
apply.)"""
import json
import time

import pytest

from bench import cell, faults, manifest

CELL = "graph500_s26.chunks"


def small_run(tmp_path, seconds=2.0):
    bench = manifest.load()
    w = manifest.workload(bench, CELL)
    config = manifest.config(bench, w)
    # the cell's configuration and limits, at a size the CPU runs in
    # seconds: 2^21 edges of a scale-16 graph in 2^17-edge shards
    config["kronecker"].update(n=16, m=16, E=1 << 21)
    config["job"]["shard_edges"] = 1 << 17
    return cell.run(config, manifest.traffic(w), 2 ** 40 + 9, seconds,
                    False, time.perf_counter(), {},
                    {"platform": "cpu", "kind": "cpu", "count": 1},
                    {"hbm_bytes_per_s": 819e9}, work_root=str(tmp_path),
                    expect_backend="xla")


def test_sound_run_is_correct(tmp_path):
    res = small_run(tmp_path)
    assert res["correct"], res["checks"]
    assert res["attempted"] >= 1 and res["failed"] == 0
    assert set(res["metrics"]) == {"edges_per_s", "setup_s"}
    assert list(res)[-1] == "checks"
    json.dumps(res)


def test_half_the_edges_left_out(tmp_path):
    with faults.half_edges():
        res = small_run(tmp_path)
    assert not res["correct"]
    assert res["checks"]["edges_outside_chunk"]["value"] > 0


def test_an_id_altered_where_it_is_drawn(tmp_path):
    with faults.id_flipped():
        res = small_run(tmp_path)
    assert not res["correct"]
    # still inside its chunk: only the statistics can see it
    assert res["checks"]["edges_outside_chunk"]["value"] == 0
    assert res["checks"]["quadrant_z_level"]["value"] > \
        res["checks"]["quadrant_z_level"]["limit"]


def test_one_draw_copied_to_every_level(tmp_path):
    with faults.level_copy():
        res = small_run(tmp_path)
    assert not res["correct"]
    # every level alone keeps its distribution: only the pairs see it
    c = res["checks"]
    assert c["edges_outside_chunk"]["value"] == 0
    assert c["quadrant_z_level"]["value"] <= c["quadrant_z_level"]["limit"]
    assert c["pair_z_level"]["value"] > c["pair_z_level"]["limit"]


def test_every_chunk_drawn_from_one_key(tmp_path):
    with faults.shared_key():
        res = small_run(tmp_path)
    assert not res["correct"]
    # chunks of one size repeat each other's draws
    assert res["checks"]["repeated_runs"]["value"] > 0


def test_a_committed_file_that_does_not_read_back(tmp_path):
    with faults.torn_file():
        res = small_run(tmp_path)
    assert not res["correct"]
    assert res["checks"]["shards_unreadable"]["value"] > 0


@pytest.mark.parametrize("k", [None, 5])
def test_reference_plan_is_the_programs(k):
    """The reference's plan of the cell's own configuration, derived from
    the definition, matches the program's chunk for chunk."""
    from repro.core.structure import KroneckerFit
    from repro.datastream import ChunkScheduler

    from bench.reference import kronecker as ref

    bench = manifest.load()
    config = manifest.config(bench, manifest.workload(bench, CELL))
    kc, se = config["kronecker"], config["job"]["shard_edges"]
    sched = ChunkScheduler(KroneckerFit(**kc), shard_edges=se, k_pref=k)
    pl = ref.plan(ref.Kronecker(**{x: kc[x] for x in "abcdnmE"}), se,
                  k_pref=k)
    assert pl.k_pref == sched.k_pref
    assert pl.n_edges.tolist() == [c.n_edges for c in sched.chunks]
    assert pl.src_prefix.tolist() == [c.src_prefix for c in sched.chunks]
    assert pl.dst_prefix.tolist() == [c.dst_prefix for c in sched.chunks]
    assert [hi - lo for lo, hi in pl.shards] == \
        [len(s.chunk_indices) for s in sched.shards]
