"""Optional ``jax.profiler`` bracketing for device-side attribution.

Host-side spans time *dispatch*, not device execution — an async jit
call returns before the kernel finishes, so a wall-clock span around it
under-reports device time (or over-reports when a later block sync pays
for it).  When a run is started with ``--jax-profile DIR``, the pipeline
additionally:

* starts a ``jax.profiler`` trace into ``DIR`` (open it in TensorBoard
  or Perfetto for the device timeline), and
* brackets the jit boundaries of the hot path —
  ``DeviceStepShardSource`` steps, chunk dispatches, the fit engine's
  bit-pair blocks — with ``TraceAnnotation`` named ranges so device
  work correlates back to pipeline stages by name.

Everything is a no-op when profiling is off (the common case):
``annotation()`` returns a shared null context, so instrumented code
pays one call and one truthiness check.  When profiling is on, a
profiler error propagates instead of being swallowed.
"""
from __future__ import annotations

import contextlib
import threading
from typing import Optional

__all__ = ["annotation", "start", "stop", "profiling"]

_lock = threading.Lock()
_active_dir: Optional[str] = None


class _NullCtx:
    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return None


_NULL = _NullCtx()


def profiling() -> bool:
    return _active_dir is not None


def annotation(name: str):
    """A ``jax.profiler.TraceAnnotation(name)`` while a profile is
    active, else a shared no-op context."""
    if _active_dir is None:
        return _NULL
    import jax
    return jax.profiler.TraceAnnotation(name)


def start(log_dir: str) -> bool:
    """Begin a device trace into ``log_dir``.  A profiler that fails to
    start raises: a run asked for a device trace must not finish
    without one."""
    global _active_dir
    with _lock:
        if _active_dir is not None:
            return True
        import jax
        jax.profiler.start_trace(log_dir)
        _active_dir = log_dir
        return True


def stop() -> Optional[str]:
    """End the device trace; returns the log dir it wrote to (or None)."""
    global _active_dir
    with _lock:
        if _active_dir is None:
            return None
        log_dir, _active_dir = _active_dir, None
        import jax
        jax.profiler.stop_trace()
        return log_dir


@contextlib.contextmanager
def trace(log_dir: Optional[str]):
    """Context form: device-profile the body when ``log_dir`` is set."""
    started = start(log_dir) if log_dir else False
    try:
        yield started
    finally:
        if started:
            stop()
