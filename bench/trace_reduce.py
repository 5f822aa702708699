"""Reduce a ``jax.profiler`` trace to the numbers the per-layer metrics
and the ``breakdown`` read.

The reduction works on plain events, ``(name, start_ns, dur_ns)``, so it
can be checked on hand-made intervals; ``device_events`` and
``host_events`` take them from the ``.xplane.pb`` a trace writes.  Times
are nanoseconds on the trace's own clock.

* busy time is the union of the device-op intervals inside the slice,
  so overlapping ops count once; the idle share is one minus busy over
  the slice;
* a kernel's time is the sum of the durations of its events, and the
  bytes it moves through HBM are those of the arrays its HLO text names
  (its results and its operands);
* each idle gap is named by the host span that overlaps it most (the
  span the host was in while the device waited).
"""
from __future__ import annotations

import dataclasses
import glob
import os
import re
from collections import defaultdict
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

Event = Tuple[str, float, float]          # (name, start_ns, dur_ns)

#: lines of a TPU device plane that hold one event per executed op
_OP_LINES = ("XLA Ops",)


def find_xplane(log_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return paths[-1]


def _stats(ev) -> Dict[str, object]:
    return {k: v for k, v in ev.stats}


def device_events(profile, prefix: str = "/device:"
                  ) -> Dict[str, List[Event]]:
    """Per device plane (a plane whose name starts with ``prefix``), its
    op events.  A TPU plane's ops are on its ``XLA Ops`` line; a plane
    without one (the CPU backend's ``/host:CPU``) contributes every event
    that names the HLO op it ran."""
    out: Dict[str, List[Event]] = {}
    for plane in profile.planes:
        if not plane.name.startswith(prefix):
            continue
        lines = [ln for ln in plane.lines if ln.name in _OP_LINES]
        evs: List[Event] = []
        if lines:
            for ln in lines:
                evs += [(e.name, float(e.start_ns), float(e.duration_ns))
                        for e in ln.events]
        else:
            for ln in plane.lines:
                evs += [(e.name, float(e.start_ns), float(e.duration_ns))
                        for e in ln.events if "hlo_op" in _stats(e)]
        out[plane.name] = evs
    return out


def host_events(profile, name: str) -> List[Event]:
    """Every host event called ``name`` (``TraceAnnotation`` ranges)."""
    evs = []
    for plane in profile.planes:
        if plane.name.startswith("/device:"):
            continue
        for ln in plane.lines:
            evs += [(e.name, float(e.start_ns), float(e.duration_ns))
                    for e in ln.events if e.name == name]
    return evs


def clip(events: Iterable[Event], lo: float, hi: float) -> List[Event]:
    """The parts of ``events`` inside ``[lo, hi)``."""
    out = []
    for name, s, d in events:
        a, b = max(s, lo), min(s + d, hi)
        if b > a:
            out.append((name, a, b - a))
    return out


def merged(events: Iterable[Event]) -> List[Tuple[float, float]]:
    """The union of the events' intervals, as sorted disjoint
    ``(start, end)`` pairs."""
    spans = sorted((s, s + d) for _, s, d in events if d > 0)
    out: List[List[float]] = []
    for s, e in spans:
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def busy_ns(events: Iterable[Event], lo: float, hi: float) -> float:
    return sum(e - s for s, e in merged(clip(events, lo, hi)))


def idle_gaps(events: Iterable[Event], lo: float, hi: float
              ) -> List[Tuple[float, float]]:
    """The ``(start, end)`` stretches of ``[lo, hi)`` with no device op."""
    gaps, t = [], lo
    for s, e in merged(clip(events, lo, hi)):
        if s > t:
            gaps.append((t, s))
        t = max(t, e)
    if hi > t:
        gaps.append((t, hi))
    return gaps


def op_name(text: str) -> str:
    """The HLO instruction's name without its numbering: a TPU trace
    names each op by its whole HLO line (``%copy.1 = f32[...] copy(...)``),
    which this shortens to ``copy``."""
    name = text.split(" = ", 1)[0].lstrip("%")
    head, _, tail = name.rpartition(".")
    return head if head and tail.isdigit() else name


def time_by_name(events: Iterable[Event]) -> Dict[str, float]:
    acc: Dict[str, float] = defaultdict(float)
    for name, _, d in events:
        acc[op_name(name)] += d
    return dict(acc)


#: bytes per element of the HLO element types
_ELEMENT_BYTES = {"pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2,
                  "f16": 2, "bf16": 2, "s32": 4, "u32": 4, "f32": 4,
                  "s64": 8, "u64": 8, "f64": 8}
_SHAPE = re.compile(r"\b(" + "|".join(_ELEMENT_BYTES) + r")\[([0-9,]*)\]")
_ATTRIBUTES = re.compile(r", (custom_call_target|backend_config|metadata)=")


def hlo_bytes(text: str) -> int:
    """Bytes of the arrays an HLO instruction's text names: its result
    (or tuple of results) and its operands, up to where its attributes
    begin.  0 where the text names no array."""
    head = _ATTRIBUTES.split(text, 1)[0]
    total = 0
    for dtype, dims in _SHAPE.findall(head):
        n = 1
        for d in filter(None, dims.split(",")):
            n *= int(d)
        total += n * _ELEMENT_BYTES[dtype]
    return total


def name_gap(gap: Tuple[float, float], spans: Sequence[Event]) -> str:
    """The host span that overlaps ``gap`` the most; among equal
    overlaps the shorter (inner) span wins.  ``"no span"`` if none."""
    best: Optional[Tuple[float, float, str]] = None
    for name, s, d in spans:
        ov = min(s + d, gap[1]) - max(s, gap[0])
        if ov <= 0:
            continue
        key = (ov, -d, name)
        if best is None or key[:2] > best[:2]:
            best = key
    return best[2] if best else "no span"


@dataclasses.dataclass
class Summary:
    """One traced slice, averaged over the devices that ran ops."""
    slice_ns: float
    busy_ns: float                    # mean over devices
    ops: List[Event]                  # every device op in the slice
    device_ops: List[Tuple[str, float]]     # top ops, seconds
    idle_gaps: List[Tuple[str, float]]      # longest gaps, seconds

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_ns / self.slice_ns


def summarize(per_device: Dict[str, List[Event]], lo: float, hi: float,
              host_spans: Sequence[Event] = (), top: int = 10) -> Summary:
    """Reduce the device events inside ``[lo, hi)``.  Devices that ran
    nothing in the slice are left out of the average; a slice in which
    no device ran anything is an error (the cell drives the device)."""
    used = {k: clip(v, lo, hi) for k, v in per_device.items()}
    used = {k: v for k, v in used.items() if v}
    if not used:
        raise ValueError("no device op ran inside the traced slice")
    busy = sum(busy_ns(v, lo, hi) for v in used.values()) / len(used)
    all_ops = [e for v in used.values() for e in v]
    by_name = sorted(time_by_name(all_ops).items(), key=lambda kv: -kv[1])
    first = sorted(used)[0]
    gaps = sorted(idle_gaps(used[first], lo, hi),
                  key=lambda g: g[0] - g[1])[:top]
    return Summary(
        slice_ns=hi - lo, busy_ns=busy, ops=all_ops,
        device_ops=[(n, t / 1e9) for n, t in by_name[:top]],
        idle_gaps=[(name_gap(g, host_spans), (g[1] - g[0]) / 1e9)
                   for g in gaps])
