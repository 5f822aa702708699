"""Shared fixtures.  NOTE: no XLA_FLAGS here — smoke tests and benches must
see the real (1-CPU) device count; only launch/dryrun.py forces 512."""
import numpy as np
import pytest


@pytest.fixture(scope="session")
def rng():
    return np.random.default_rng(0)


@pytest.fixture
def prng_chunk_program(monkeypatch):
    """``sampler._prng_chunk`` jitted afresh under a ``RetraceRecorder``:
    its cache starts empty whatever ran before in the process, and the
    recorder counts its traces (it sees only jits made while it is
    active, and the module's own is made at import)."""
    import jax

    from repro.analysis.retrace import RetraceRecorder
    from repro.core import sampler

    with RetraceRecorder() as rec:
        monkeypatch.setattr(sampler, "_prng_chunk", jax.jit(
            sampler._prng_chunk.__wrapped__,
            static_argnames=("n", "m", "n_pad", "block", "interpret")))
        yield rec


@pytest.fixture
def prng_chunk_compiles():
    """The backend compiles of ``sampler._prng_chunk`` while the test
    runs, counted from JAX's own compile events as the benchmark counts
    its compiles."""
    import jax

    seen = []

    def on_event(name, _dur, fun_name=None, **_kw):
        if (name == "/jax/core/compile/backend_compile_duration"
                and fun_name == "jit(_prng_chunk)"):
            seen.append(name)

    jax.monitoring.register_event_duration_secs_listener(on_event)
    yield seen
    jax.monitoring.unregister_event_duration_listener(on_event)
