"""The shard order covers the plan and stops at the deadline, and the
executor commits shards out of id order in a way that resume and deep
verify accept."""
import hashlib
import os

import numpy as np

from bench import order


def test_bit_reversed_covers_every_shard_once():
    for n in (1, 2, 3, 7, 8, 132, 1000):
        o = order.bit_reversed(n)
        assert sorted(o) == list(range(n))
    assert order.bit_reversed(132)[:6] == [0, 128, 64, 32, 96, 16]


def test_every_prefix_spreads_over_the_plan():
    n = 132
    o = order.bit_reversed(n)
    for k in (8, 16, 32, 64):
        # each quarter of the plan gets a quarter of the prefix, give or
        # take one shard either way
        quarters = np.bincount(np.asarray(o[:k]) * 4 // n, minlength=4)
        assert np.abs(quarters - k / 4).max() <= 1


def test_sequence_stops_at_the_deadline():
    now = [0.0]
    seen = []
    seq = order.UntilDeadline(list("abcdef"), deadline=3.0,
                              on_next=seen.append, clock=lambda: now[0])
    taken = []
    for item in seq:
        taken.append(item)
        now[0] += 1.0           # each record takes a second
    assert taken == list("abc") and seq.handed_out == 3
    assert seen == [0, 1, 2] and len(seq) == 6


def _digest(out_dir):
    h = hashlib.sha256()
    for f in sorted(os.listdir(out_dir)):
        if f.startswith("shard-"):
            with open(os.path.join(out_dir, f), "rb") as fh:
                h.update(f.encode() + fh.read())
    return h.hexdigest()


def test_out_of_order_commits_resume_and_verify(tmp_path):
    from repro.core.structure import KroneckerFit
    from repro.datastream import DatasetJob, ShardExecutor, ShardWriter

    fit = KroneckerFit(0.57, 0.19, 0.19, 0.05, n=12, m=12, E=1 << 16)
    kw = dict(shard_edges=1 << 12, seed=3, backend="xla")
    job = DatasetJob(fit, str(tmp_path / "window"), **kw)
    manifest = job.plan()
    n = len(manifest.shards)
    assert n >= 8
    writer = ShardWriter(job.out_dir, manifest)
    ticks = iter(range(1000))
    seq = order.UntilDeadline(
        [manifest.shards[i] for i in order.bit_reversed(n)],
        deadline=5, clock=lambda: next(ticks))
    ShardExecutor(job.source, writer, seed=job.seed,
                  pipeline_depth=2).run(seq)
    writer.checkpoint()
    done = sorted(job.dataset(allow_partial=True).manifest.done_ids())
    assert done == sorted(order.bit_reversed(n)[:5])
    # the committed shards verify deep; only the others are missing
    assert sorted(job.verify(deep=True)) == sorted(
        f"shard {i}: not materialized" for i in range(n) if i not in done)
    job.resume()
    assert job.dataset().manifest.is_complete()
    assert job.verify(deep=True) == []
    serial = DatasetJob(fit, str(tmp_path / "serial"), pipeline_depth=0,
                        **kw)
    serial.run()
    assert _digest(job.out_dir) == _digest(serial.out_dir)
