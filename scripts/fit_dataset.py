#!/usr/bin/env python
"""Fit θ (and fit provenance) from a materialized dataset — the inverse
of ``generate_dataset.py``, closing the fit → generate → refit loop:

    PYTHONPATH=src python scripts/fit_dataset.py \
        --dataset /tmp/ds --out /tmp/fit.json

reads the dataset manifest, streams every shard through the one-pass
accumulators of ``repro.core.fit_engine`` (jit-batched bit-pair MLE,
bounded-memory degree sketches, order-invariant row sample) and writes a
deterministic fit JSON: a ``KroneckerFit`` under ``"fit"`` plus the
``"provenance"`` block (per-level bit-pair counts, sketch digests,
candidate calibration scores, sample identity, feature moments).  The
output is accepted directly by ``generate_dataset.py --fit``.

Peak memory is bounded by ``--chunk-rows`` (plus the fixed-size
sketches), never by the dataset; int64 wide-id datasets fit without
jax x64.  ``--check-theta T`` exits non-zero when the recovered θ
deviates from the manifest's generator θ by more than ``T`` in any of
(a, b, c, d) — the CI round-trip gate.
"""
from __future__ import annotations

import argparse
import os
import sys
import time


def parse_count(s: str) -> int:
    """'1e7', '10_000', '1<<20' style counts (see repro.utils; lazy so
    ``--help`` works without PYTHONPATH)."""
    from repro.utils import parse_count as _parse_count
    return _parse_count(s)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description=__doc__,
        formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("--dataset", required=True,
                    help="dataset directory (manifest.json inside)")
    ap.add_argument("--out", required=True, help="fit JSON output path")
    ap.add_argument("--chunk-rows", default="1<<20",
                    help="rows per fit chunk (the memory bound)")
    ap.add_argument("--sample-rows", default="100000",
                    help="row-sample size feeding feature moments / "
                         "provenance")
    ap.add_argument("--kmax", type=int, default=2048,
                    help="degree-sketch histogram bins (tail clipped)")
    ap.add_argument("--seed", type=int, default=0,
                    help="row-sample priority seed")
    ap.add_argument("--noise", type=float, default=0.0,
                    help="App. 9 θ-noise amplitude recorded on the fit")
    ap.add_argument("--no-calibrate", action="store_true",
                    help="skip the candidate calibration ladder (pure "
                         "MLE + Eq. 6 refinement)")
    ap.add_argument("--structure-only", action="store_true",
                    help="ignore feature columns (skip moments/sample "
                         "feature provenance)")
    ap.add_argument("--check-theta", type=float, default=None,
                    metavar="TOL",
                    help="exit 1 unless max |θ_fit − θ_manifest| <= TOL "
                         "(round-trip verification)")
    ap.add_argument("--trace", nargs="?", const="auto", default=None,
                    metavar="PATH",
                    help="record a span event log (crash-safe JSONL) of "
                         "the fit pass; with no PATH it lands next to "
                         "--out as OUT.trace.jsonl. Feed it to "
                         "scripts/report_run.py")
    ap.add_argument("--metrics-out", default=None, metavar="PATH",
                    help="write accumulate/fit timings as a unified "
                         "BENCH-schema JSON")
    ap.add_argument("--jax-profile", default=None, metavar="DIR",
                    help="capture a jax.profiler device trace into DIR")
    args = ap.parse_args(argv)
    if argv is None:
        # a command-line run keeps its compiled programs across runs
        from repro.utils import use_compile_cache
        use_compile_cache()

    from repro.core import fit_engine
    from repro.datastream.fitsource import DatasetFitSource
    from repro.obs import JsonlSink, Tracer, jaxprof, write_bench

    tracer = Tracer()
    trace_path = None
    if args.trace is not None:
        trace_path = (args.out + ".trace.jsonl"
                      if args.trace == "auto" else args.trace)
        os.makedirs(os.path.dirname(trace_path) or ".", exist_ok=True)
        tracer.add_sink(JsonlSink(trace_path))

    cols = (("src", "dst") if args.structure_only
            else ("src", "dst", "cont", "cat"))
    try:
        source = DatasetFitSource(args.dataset,
                                  chunk_rows=parse_count(args.chunk_rows),
                                  columns=cols)
    except (FileNotFoundError, RuntimeError, ValueError) as e:
        raise SystemExit(f"error: {e}")
    print(f"fit plan: {source.total_rows:,} rows over "
          f"{len(source.ds)} shards, 2^{source.ds.manifest.fit['n']}×"
          f"2^{source.ds.manifest.fit['m']} ids "
          f"({source.ds.manifest.dtype}), chunk_rows="
          f"{parse_count(args.chunk_rows):,}", file=sys.stderr)
    t0 = time.time()
    with jaxprof.trace(args.jax_profile):
        stats = fit_engine.accumulate(
            source, sample_rows=parse_count(args.sample_rows),
            seed=args.seed, kmax=args.kmax, tracer=tracer)
        t_acc = time.time() - t0
        t0 = time.time()
        with tracer.span("fit.theta"):
            fit, prov = fit_engine.fit_structure_streamed(
                stats, noise=args.noise, calibrate=not args.no_calibrate)
        t_fit = time.time() - t0
    tracer.close()
    # record which generation path produced the input dataset: backend
    # names the PRNG stream, executor carries the byte-transparent knobs
    # (pipeline depth, host workers, fused device-resident generation) —
    # provenance for reproducing the exact run, never validated
    man = source.ds.manifest
    prov["generator"] = {"backend": man.backend, "mode": man.mode,
                         "executor": man.executor}
    text = fit_engine.fit_to_json(fit, prov)
    tmp = args.out + ".tmp"
    with open(tmp, "w") as f:
        f.write(text)
    os.replace(tmp, args.out)

    rate = stats.rows / max(t_acc, 1e-9)
    print(f"accumulated {stats.rows:,} rows in {t_acc:.1f}s "
          f"({rate:,.0f} rows/s), θ-fit in {t_fit:.1f}s "
          f"(chosen: {prov.get('chosen')})", file=sys.stderr)
    print(f"θ = ({fit.a:.4f}, {fit.b:.4f}, {fit.c:.4f}, {fit.d:.4f})  "
          f"MLE = ({', '.join(f'{x:.4f}' for x in prov['theta_mle'])})",
          file=sys.stderr)

    gen_fit = source.ds.manifest.fit
    err = max(abs(fit.a - gen_fit["a"]), abs(fit.b - gen_fit["b"]),
              abs(fit.c - gen_fit["c"]), abs(fit.d - gen_fit["d"]))
    print(f"round-trip: max |θ_fit − θ_gen| = {err:.4f}", file=sys.stderr)
    if trace_path:
        print(f"trace: {trace_path}", file=sys.stderr)
    if args.metrics_out:
        write_bench("fit_dataset",
                    {"timings": {"accumulate_s": t_acc, "theta_fit_s": t_fit,
                                 "fit_read_s": tracer.total("fit.read"),
                                 "fit_update_s": tracer.total("fit.update"),
                                 "fit_finalize_s": tracer.total("fit.finalize")},
                     "rows": stats.rows, "n_chunks": stats.n_chunks,
                     "theta_err": err},
                    args.metrics_out)
        print(f"metrics: {args.metrics_out}", file=sys.stderr)
    if args.check_theta is not None and err > args.check_theta:
        print(f"CHECK FAILED: {err:.4f} > tolerance {args.check_theta}",
              file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
