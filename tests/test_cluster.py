"""Multi-process generation cluster (repro.distributed.cluster):
per-worker journal namespacing, strict manifest merge, worker-stripe
entry points (API + CLI), torn-journal replay, and the coordinator's
crash-rebalance byte identity."""
import dataclasses
import hashlib
import importlib.util
import json
import os
import sys

import pytest

from repro.core.structure import KroneckerFit
from repro.datastream import (DatasetJob, Manifest, ShardedGraphDataset,
                              worker_journal_name, worker_journal_paths)
from repro.datastream.writer import JOURNAL_NAME, MANIFEST_NAME
from repro.distributed.cluster import ClusterCoordinator, ClusterError
from repro.distributed.launcher import (WorkerProcess, child_env,
                                       repro_pythonpath)

FIT = KroneckerFit(a=0.45, b=0.22, c=0.2, d=0.13, n=10, m=10, E=8_000)
SHARD_EDGES = 2_000
SEED = 3
SCRIPT = os.path.join(os.path.dirname(__file__), "..", "scripts",
                      "generate_dataset.py")


def _job(out, num_workers=1):
    return DatasetJob(FIT, str(out), shard_edges=SHARD_EDGES, seed=SEED,
                      num_workers=num_workers, double_buffered=False,
                      pipeline_depth=0)


def _file_hashes(path):
    return {f: hashlib.md5(open(os.path.join(path, f), "rb").read())
            .hexdigest()
            for f in sorted(os.listdir(path)) if f.endswith(".npy")}


def _manifest_sans_placement(path):
    """manifest.json minus placement provenance: worker count, executor
    knobs and per-shard worker assignment don't change a byte of data."""
    with open(os.path.join(path, MANIFEST_NAME)) as f:
        d = json.load(f)
    d.pop("executor", None)
    d.pop("num_workers", None)
    for s in d["shards"]:
        s.pop("worker", None)
    return d


@pytest.fixture(scope="module")
def serial_ref(tmp_path_factory):
    """The uninterrupted single-process reference every cluster result
    must be byte-identical to."""
    out = str(tmp_path_factory.mktemp("serial_ref"))
    manifest = _job(out).run()
    assert manifest.is_complete()
    return out, manifest


# -- journal namespacing -----------------------------------------------------

def test_worker_journal_paths_sort_numerically(tmp_path):
    for k in (10, 0, 2):
        (tmp_path / worker_journal_name(k)).write_text("")
    (tmp_path / "journal.wx.jsonl").write_text("")   # not a worker journal
    (tmp_path / JOURNAL_NAME).write_text("")
    paths = worker_journal_paths(str(tmp_path))
    assert [os.path.basename(p) for p in paths] == \
        ["journal.w0.jsonl", "journal.w2.jsonl", "journal.w10.jsonl"]
    assert worker_journal_paths(str(tmp_path / "missing")) == []


# -- worker-stripe runs + merge ----------------------------------------------

def test_worker_stripes_merge_byte_identical_to_serial(serial_ref, tmp_path):
    ref_out, ref_manifest = serial_ref
    out = str(tmp_path / "ds")
    _job(out, num_workers=2).plan()
    # each stripe runs the full executor, appending to its own journal
    # and never rewriting manifest.json
    manifest_bytes = open(os.path.join(out, MANIFEST_NAME), "rb").read()
    for k in (0, 1):
        _job(out, num_workers=2).run_worker(k)
        assert os.path.exists(os.path.join(out, worker_journal_name(k)))
    assert open(os.path.join(out, MANIFEST_NAME), "rb").read() == \
        manifest_bytes
    assert not os.path.exists(os.path.join(out, JOURNAL_NAME))
    # the coordinator's sync: strict merge, compact, drop journals
    merged = Manifest.load(out)
    stats = merged.merge_worker_journals(out)
    assert set(stats) == {"journal.w0.jsonl", "journal.w1.jsonl"}
    assert sum(s["shards"] for s in stats.values()) == \
        len(merged.shards)
    assert all(s["shards"] > 0 for s in stats.values())
    assert sum(s["edges"] for s in stats.values()) == FIT.E
    merged.save(out)
    for p in worker_journal_paths(out):
        os.remove(p)
    # merged progress equals the serial run's
    assert merged.is_complete()
    assert merged.done_edges() == ref_manifest.done_edges() == FIT.E
    # and the dataset is byte-identical modulo placement provenance
    assert _file_hashes(out) == _file_hashes(ref_out)
    assert _manifest_sans_placement(out) == _manifest_sans_placement(ref_out)
    ds = ShardedGraphDataset(out)
    assert ds.total_edges == FIT.E and not ds.verify(deep=True)


def test_merge_handles_out_of_order_journals(serial_ref, tmp_path):
    out = str(tmp_path / "ds")
    _job(out, num_workers=2).plan()
    for k in (0, 1):
        _job(out, num_workers=2).run_worker(k)
    # a journal's records can land in any order (async flush commits
    # shards out of submission order): reverse both journals
    for p in worker_journal_paths(out):
        lines = open(p).read().splitlines()
        with open(p, "w") as f:
            f.write("\n".join(reversed(lines)) + "\n")
    merged = Manifest.load(out)
    merged.merge_worker_journals(out)
    assert merged.is_complete() and merged.done_edges() == FIT.E
    # merging twice (coordinator retry after a crash before cleanup)
    # is idempotent
    merged.save(out)
    again = Manifest.load(out)
    again.merge_worker_journals(out)
    assert again.to_json() == merged.to_json()


def test_merge_rejects_duplicate_shard_across_journals(tmp_path):
    out = str(tmp_path / "ds")
    _job(out, num_workers=2).plan()
    _job(out, num_workers=2).run_worker(0)
    w0 = os.path.join(out, worker_journal_name(0))
    first = open(w0).read().splitlines()[0]
    with open(os.path.join(out, worker_journal_name(1)), "w") as f:
        f.write(first + "\n")
    merged = Manifest.load(out)
    with pytest.raises(ValueError, match="stripes overlapped"):
        merged.merge_worker_journals(out)


# -- torn journal tails (satellite: _replay_journal crash tolerance) ---------

def test_replay_skips_torn_final_journal_line(tmp_path):
    out = str(tmp_path / "ds")
    job = _job(out)
    job.run(max_shards=2)
    journal = os.path.join(out, JOURNAL_NAME)
    # the run's final checkpoint compacted the journal; journal a record
    # again then tear it mid-append (SIGKILL): a complete record line
    # followed by a truncated half-record with no newline
    m = Manifest.load(out)
    done = [s for s in m.shards if s.status == "done"]
    assert len(done) == 2
    line = json.dumps(done[0].to_json())
    with open(journal, "a") as f:
        f.write(line + "\n")
        f.write(json.dumps(done[1].to_json())[:25])
    replayed = Manifest.load(out)          # must not raise
    assert [s.shard_id for s in replayed.shards if s.status == "done"] \
        == [s.shard_id for s in done]
    # resume completes the dataset despite the torn tail
    final = _job(out).run(resume=True)
    assert final.is_complete()


def test_merge_skips_torn_worker_journal_tail(tmp_path):
    out = str(tmp_path / "ds")
    _job(out, num_workers=2).plan()
    _job(out, num_workers=2).run_worker(0)
    w0 = os.path.join(out, worker_journal_name(0))
    lines = open(w0).read().splitlines()
    with open(w0, "a") as f:
        f.write(lines[-1][:30])            # torn re-append, no newline
        f.write("\nnot json either")       # and a corrupt complete line
    merged = Manifest.load(out)            # must not raise
    stats = merged.merge_worker_journals(out)
    assert stats["journal.w0.jsonl"]["shards"] == len(lines)


# -- run_worker validation ---------------------------------------------------

def test_run_worker_requires_existing_plan(tmp_path):
    with pytest.raises(FileNotFoundError, match="plans first"):
        _job(str(tmp_path / "nope"), num_workers=2).run_worker(0)


def test_run_worker_validates_stripe_count(tmp_path):
    out = str(tmp_path / "ds")
    _job(out, num_workers=2).plan()
    with pytest.raises(ValueError, match="num_workers=2"):
        _job(out, num_workers=3).run_worker(0)
    with pytest.raises(ValueError, match="stripes"):
        _job(out, num_workers=2).run_worker(2)


# -- CLI stripe mode ---------------------------------------------------------

def _load_script(name):
    path = os.path.join(os.path.dirname(__file__), "..", "scripts",
                        f"{name}.py")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_cli_worker_stripe_mode(serial_ref, tmp_path):
    ref_out, _ = serial_ref
    gen_cli = _load_script("generate_dataset")
    fit_json = str(tmp_path / "fit.json")
    with open(fit_json, "w") as f:
        json.dump(dataclasses.asdict(FIT), f)
    out = str(tmp_path / "ds")
    base = ["--fit", fit_json, "--shard-edges", str(SHARD_EDGES),
            "--out", out, "--seed", str(SEED), "--serial"]
    # --worker-id needs --num-workers, and a plan to run against
    with pytest.raises(SystemExit):
        gen_cli.main(base + ["--worker-id", "0"])
    with pytest.raises(SystemExit):
        gen_cli.main(base + ["--num-workers", "2", "--worker-id", "0"])
    _job(out, num_workers=2).plan()
    # stripe count must match the plan's
    with pytest.raises(SystemExit):
        gen_cli.main(base + ["--num-workers", "3", "--worker-id", "0"])
    for k in (0, 1):
        rc = gen_cli.main(base + ["--num-workers", "2",
                                  "--worker-id", str(k),
                                  "--trace", "--metrics-out",
                                  str(tmp_path / "metrics.json")])
        assert rc == 0
        # per-worker artifact namespacing
        assert os.path.exists(os.path.join(out, f"trace.w{k}.jsonl"))
        assert os.path.exists(str(tmp_path / f"metrics.w{k}.json"))
    merged = Manifest.load(out)
    merged.merge_worker_journals(out)
    assert merged.is_complete()
    assert _file_hashes(out) == _file_hashes(ref_out)


def test_cli_refuses_more_workers_than_chips(tmp_path, monkeypatch):
    """A chip belongs to one process at a time: on a TPU host the
    planning step refuses more worker processes than chips before a
    manifest lands on disk.  The TPU host is simulated by steering
    ``jax.default_backend`` / ``jax.local_device_count``."""
    import jax
    gen_cli = _load_script("generate_dataset")
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(jax, "local_device_count", lambda: 1)
    out = str(tmp_path / "ds")
    with pytest.raises(SystemExit, match="exceeds the 1 TPU chip"):
        gen_cli.main(["--fit", "demo", "--edges", "8000", "--shard-edges",
                      str(SHARD_EDGES), "--out", out, "--plan-only",
                      "--num-workers", "2"])
    assert not Manifest.exists(out)


def test_coordinator_process_initializes_no_jax_backend(serial_ref,
                                                        tmp_path):
    """The ``--num-workers`` coordinator leaves JAX uninitialized for its
    whole run — the planner and the workers are child processes, and on
    a TPU host a parent that holds the chip would lock them out.  Output
    stays byte-identical to the single-process reference."""
    import subprocess
    import textwrap
    ref_out, _ = serial_ref
    fit_json = str(tmp_path / "fit.json")
    with open(fit_json, "w") as f:
        json.dump(dataclasses.asdict(FIT), f)
    out = str(tmp_path / "ds")
    argv = [SCRIPT, "--fit", fit_json, "--shard-edges", str(SHARD_EDGES),
            "--out", out, "--seed", str(SEED), "--serial",
            "--num-workers", "2", "--verify"]
    body = textwrap.dedent(f"""
        import runpy, sys
        from jax._src import xla_bridge
        from repro.distributed import cluster
        run = cluster.ClusterCoordinator.run
        def checked(self):
            assert not xla_bridge.backends_are_initialized(), "at spawn"
            return run(self)
        cluster.ClusterCoordinator.run = checked
        sys.argv = {argv!r}
        try:
            runpy.run_path(sys.argv[0], run_name="__main__")
        except SystemExit as e:
            assert e.code in (0, None), e.code
        assert not xla_bridge.backends_are_initialized(), "after the run"
        print("no backend")
    """)
    r = subprocess.run([sys.executable, "-c", body], capture_output=True,
                       text=True, env=child_env())
    assert r.returncode == 0 and "no backend" in r.stdout, r.stderr[-3000:]
    assert _file_hashes(out) == _file_hashes(ref_out)


# -- launcher ----------------------------------------------------------------

def test_worker_process_tails_only_complete_lines(tmp_path):
    journal = str(tmp_path / "j.jsonl")
    proc = WorkerProcess(
        0, [sys.executable, "-c", "import time; time.sleep(5)"],
        journal_path=journal, log_dir=str(tmp_path))
    try:
        assert proc.alive()
        assert proc.poll_journal() == []          # no journal yet
        with open(journal, "w") as f:
            f.write('{"status": "done", "n_edges": 7}\n{"status": "do')
            f.flush()
        assert proc.poll_journal() == [{"status": "done", "n_edges": 7}]
        assert proc.poll_journal() == []          # partial line deferred
        with open(journal, "a") as f:
            f.write('ne", "n_edges": 5}\n')
        assert proc.poll_journal() == [{"status": "done", "n_edges": 5}]
    finally:
        proc.kill()
    assert not proc.alive() and proc.returncode is not None
    assert os.path.exists(proc.log_path)


def test_repro_pythonpath_resolves_package_dir():
    root = repro_pythonpath()
    assert os.path.isdir(os.path.join(root, "repro", "datastream"))


# -- the coordinator ---------------------------------------------------------

#: the slow coordinator tests use a bigger plan (≈12 shards) so each
#: stripe holds several shards — killing a worker after its first
#: commit then reliably leaves an uncommitted suffix to rebalance
FIT_BIG = KroneckerFit(a=0.45, b=0.22, c=0.2, d=0.13, n=11, m=11,
                       E=24_000)


def _job_big(out, num_workers=1):
    return DatasetJob(FIT_BIG, str(out), shard_edges=SHARD_EDGES,
                      seed=SEED, num_workers=num_workers,
                      double_buffered=False, pipeline_depth=0)


def _worker_argv_builder(fit_json, out):
    def build(worker_id, num_workers):
        return [sys.executable, SCRIPT, "--fit", fit_json,
                "--shard-edges", str(SHARD_EDGES), "--out", out,
                "--seed", str(SEED), "--serial",
                "--num-workers", str(num_workers),
                "--worker-id", str(worker_id)]
    return build


def test_coordinator_requires_plan(tmp_path):
    with pytest.raises(ClusterError, match="no manifest"):
        ClusterCoordinator(str(tmp_path), lambda w, W: ["true"],
                           num_workers=2).run()


@pytest.fixture(scope="module")
def serial_ref_big(tmp_path_factory):
    out = str(tmp_path_factory.mktemp("serial_ref_big"))
    manifest = _job_big(out).run()
    assert manifest.is_complete()
    return out, manifest


@pytest.fixture
def fit_json_big(tmp_path):
    path = str(tmp_path / "fit.json")
    with open(path, "w") as f:
        json.dump(dataclasses.asdict(FIT_BIG), f)
    return path


@pytest.mark.slow
def test_coordinator_two_workers_byte_identical(serial_ref_big, tmp_path,
                                                fit_json_big):
    ref_out, _ = serial_ref_big
    out = str(tmp_path / "ds")
    _job_big(out, num_workers=2).plan()
    coord = ClusterCoordinator(out,
                               _worker_argv_builder(fit_json_big, out),
                               num_workers=2)
    manifest = coord.run()
    assert manifest.is_complete() and manifest.done_edges() == FIT_BIG.E
    assert len(coord.report["rounds"]) == 1
    assert coord.report["rounds"][0]["deaths"] == 0
    assert worker_journal_paths(out) == []       # merged and cleaned up
    assert _file_hashes(out) == _file_hashes(ref_out)
    assert _manifest_sans_placement(out) == _manifest_sans_placement(ref_out)
    assert not ShardedGraphDataset(out).verify(deep=True)


@pytest.mark.slow
def test_coordinator_kill_rebalance_byte_identical(serial_ref_big,
                                                   tmp_path, fit_json_big):
    ref_out, _ = serial_ref_big
    out = str(tmp_path / "ds")
    _job_big(out, num_workers=2).plan()
    coord = ClusterCoordinator(out,
                               _worker_argv_builder(fit_json_big, out),
                               num_workers=2, poll_s=0.02,
                               kill_after={1: 1})
    manifest = coord.run()
    assert manifest.is_complete() and manifest.done_edges() == FIT_BIG.E
    rounds = coord.report["rounds"]
    assert rounds[0]["deaths"] == 1
    assert rounds[0]["workers"]["1"]["killed"]
    # the dead worker's suffix re-striped across the survivor count
    assert len(rounds) >= 2 and rounds[1]["num_workers"] == 1
    assert Manifest.load(out).num_workers == 1
    assert _file_hashes(out) == _file_hashes(ref_out)
    assert not ShardedGraphDataset(out).verify(deep=True)
