"""Worker-process launcher for multi-process dataset generation.

:class:`WorkerProcess` wraps one spawned stripe worker: it builds the
environment (``PYTHONPATH`` pointing at this checkout's ``src`` so the
child imports the same ``repro``), redirects the child's stdout/stderr
to ``worker.w{k}.log`` next to the dataset, and **tails the worker's
journal incrementally** — ``poll_journal()`` reads only the bytes
appended since the last poll and only up to the last complete line, so
a record the worker is mid-append on is never half-parsed (the next
poll picks it up whole).  The coordinator in
:mod:`repro.distributed.cluster` drives these; nothing here knows about
shard semantics beyond "a journal line is one JSON object".
"""
from __future__ import annotations

import json
import os
import signal
import subprocess
import sys
from typing import Any, Dict, List, Optional, Sequence

import repro

__all__ = ["WorkerProcess", "child_env", "repro_pythonpath",
           "worker_log_name"]


def repro_pythonpath() -> str:
    """The ``src`` directory the running ``repro`` package was imported
    from — prepended to the child's ``PYTHONPATH`` so spawned workers
    resolve the same code as the coordinator."""
    init = getattr(repro, "__file__", None)
    if init:
        return os.path.dirname(os.path.dirname(os.path.abspath(init)))
    # namespace package (no __init__.py): __path__ holds the package dir
    return os.path.dirname(os.path.abspath(list(repro.__path__)[0]))


def child_env(extra: Optional[Dict[str, str]] = None) -> Dict[str, str]:
    """This process's environment with ``repro_pythonpath()`` prepended to
    ``PYTHONPATH`` (plus ``extra``), for children that run this
    checkout's code."""
    env = dict(os.environ)
    env["PYTHONPATH"] = os.pathsep.join(
        [repro_pythonpath()] +
        ([env["PYTHONPATH"]] if env.get("PYTHONPATH") else []))
    env.update(extra or {})
    return env


def worker_log_name(worker_id: int) -> str:
    return f"worker.w{int(worker_id)}.log"


class WorkerProcess:
    """One spawned worker stripe: process handle + incremental journal
    tail.

    ``argv`` is the full command line (typically
    ``[sys.executable, generate_dataset.py, ..., --worker-id, k]``).
    The journal at ``journal_path`` need not exist yet — the worker
    creates it on its first committed shard.
    """

    def __init__(self, worker_id: int, argv: Sequence[str],
                 journal_path: str, log_dir: Optional[str] = None,
                 env: Optional[Dict[str, str]] = None):
        self.worker_id = int(worker_id)
        self.argv = list(argv)
        self.journal_path = journal_path
        self._offset = 0          # bytes of journal already consumed
        self._carry = b""         # partial line awaiting its newline
        self.log_path: Optional[str] = None
        self._log_file = None
        stdout = subprocess.DEVNULL
        if log_dir is not None:
            self.log_path = os.path.join(
                log_dir, worker_log_name(self.worker_id))
            self._log_file = open(self.log_path, "ab")
            stdout = self._log_file
        self.proc = subprocess.Popen(
            self.argv, stdout=stdout, stderr=subprocess.STDOUT,
            env=child_env(env))

    # -- lifecycle ---------------------------------------------------------
    def alive(self) -> bool:
        return self.proc.poll() is None

    @property
    def returncode(self) -> Optional[int]:
        return self.proc.poll()

    def kill(self, grace_s: float = 0.0) -> None:
        """SIGKILL the worker (after ``grace_s`` of SIGTERM first, if
        given).  Used by the coordinator on shutdown and by the
        fault-injection path in tests/CI."""
        if not self.alive():
            self._close_log()
            return
        try:
            if grace_s > 0:
                self.proc.send_signal(signal.SIGTERM)
                try:
                    self.proc.wait(timeout=grace_s)
                except subprocess.TimeoutExpired:
                    pass
            if self.alive():
                self.proc.kill()
            self.proc.wait()
        finally:
            self._close_log()

    def wait(self, timeout: Optional[float] = None) -> Optional[int]:
        try:
            rc = self.proc.wait(timeout=timeout)
        except subprocess.TimeoutExpired:
            return None
        self._close_log()
        return rc

    def _close_log(self) -> None:
        if self._log_file is not None:
            try:
                self._log_file.close()
            finally:
                self._log_file = None

    # -- journal tail ------------------------------------------------------
    def poll_journal(self) -> List[Dict[str, Any]]:
        """New complete journal records since the last poll.

        Reads from the saved byte offset; bytes after the last ``\\n``
        are carried over rather than parsed, so a record being appended
        when we read is deferred, never torn.  Corrupt complete lines
        (shouldn't happen — each journal has one writer) are skipped
        with the same tolerance as :func:`repro.obs.sinks.iter_events`.
        """
        try:
            with open(self.journal_path, "rb") as f:
                f.seek(self._offset)
                chunk = f.read()
        except OSError:
            return []
        if not chunk:
            return []
        self._offset += len(chunk)
        data = self._carry + chunk
        head, sep, tail = data.rpartition(b"\n")
        if not sep:                       # no newline yet: all carry
            self._carry = data
            return []
        self._carry = tail
        out: List[Dict[str, Any]] = []
        for line in head.splitlines():
            line = line.strip()
            if not line:
                continue
            try:
                rec = json.loads(line.decode("utf-8"))
            except (ValueError, UnicodeDecodeError):
                continue
            if isinstance(rec, dict):
                out.append(rec)
        return out

    def __repr__(self) -> str:  # pragma: no cover - debugging aid
        state = "alive" if self.alive() else f"rc={self.returncode}"
        return f"WorkerProcess(w{self.worker_id}, {state})"


def python_argv(script: str, *flags: str) -> List[str]:
    """``[sys.executable, script, *flags]`` — tiny helper so call sites
    don't each reach for ``sys.executable``."""
    return [sys.executable, script, *flags]
