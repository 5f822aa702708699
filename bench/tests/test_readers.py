"""The struct stage's readers of the program's compile, dispatch and
fetch spans and counters, on hand-made readings."""
import pytest

from bench import manifest, peaks
from bench.cell import Context


def _read(metric, spans, counters=None, window_s=20.0):
    ctx = Context(window_s=window_s, spans=spans, counters=counters or {},
                  compiles_in_window=0, trace=None,
                  peaks=peaks.peaks("TPU v5 lite"))
    return manifest._load_reader(metric)(ctx)


COMPILE = {"compile.trace": 2.0, "compile.lower": 3.0,
           "compile.backend": 10.0}


def test_compile_pct():
    assert _read("compile_pct", {**COMPILE, "struct": 19.0}) \
        == pytest.approx(75.0)
    # a program that watches its compiles and compiled nothing reads 0
    zero = dict.fromkeys(COMPILE, 0.0)
    assert _read("compile_pct", zero) == 0.0
    # one that does not watch them reads nothing
    assert _read("compile_pct", {"struct": 19.0}) is None


def test_dispatch_ms_per_chunk():
    spans = {"struct.dispatch": 19.5}
    assert _read("dispatch_ms_per_chunk", spans, {"struct.chunks": 39.0}) \
        == pytest.approx(500.0)
    assert _read("dispatch_ms_per_chunk", spans) is None
    assert _read("dispatch_ms_per_chunk", spans,
                 {"struct.chunks": 0.0}) is None
    assert _read("dispatch_ms_per_chunk", {},
                 {"struct.chunks": 39.0}) is None


def test_fetch_GBps():
    spans = {"struct.fetch": 0.25}
    assert _read("fetch_GBps", spans, {"struct.bytes_fetched": 5e8}) \
        == pytest.approx(2.0)
    assert _read("fetch_GBps", spans) is None
    assert _read("fetch_GBps", {"struct.fetch": 0.0},
                 {"struct.bytes_fetched": 5e8}) is None
    assert _read("fetch_GBps", {}, {"struct.bytes_fetched": 5e8}) is None
