"""Mesh step (the ``shard_map`` generation step): the least time its work
in the traced slice could take at the chip's HBM peak, over the device
time of the step's ops, per chip.

The work counted is what the step must write, whatever computes it: its
id columns, 8 B per edge of int32 ids (src and dst).  They are found in
the trace as the results of the ops that write the step's outputs: the
integer arrays of the largest element count in the slice (a chip's share
of a step, one per id column and execution).  The intermediates an
implementation holds are not counted, nor are the key and θ reads, nor
the threefry arithmetic (no VPU peak is published for the chip), so the
least time is counted low and this reads at or below the true share.
Every device op in the slice is the step's in this cell (the window runs
nothing else on the chips), and their durations are summed over the
chips, as the bytes are: the ratio is the share per chip.  A slice with
no integer result reads nothing."""
import re

#: bytes per element of the integer types ids are written in
ID_BYTES = {"s32": 4, "s64": 8}
_ARRAY = re.compile(r"\b(s32|s64)\[([0-9,]*)\]")


def results(text: str):
    """``(dtype, elements)`` of each integer array an HLO instruction's
    text gives as its result (a single array or a tuple of them)."""
    _, eq, rest = text.partition(" = ")
    if not eq:
        return []
    if rest.startswith("("):
        depth = 0
        for i, ch in enumerate(rest):
            depth += {"(": 1, ")": -1}.get(ch, 0)
            if depth == 0:
                head = rest[:i + 1]
                break
        else:
            head = rest
    else:
        head = rest.split(" ", 1)[0]
    out = []
    for dtype, dims in _ARRAY.findall(head):
        n = 1
        for d in filter(None, dims.split(",")):
            n *= int(d)
        out.append((dtype, n))
    return out


def output_bytes(ops) -> int:
    """Bytes of the step's id columns written in ``ops``: every integer
    result of the largest element count among them."""
    found = [r for name, _, _ in ops for r in results(name)]
    if not found:
        return 0
    widest = max(n for _, n in found)
    return sum(ID_BYTES[t] * n for t, n in found if n == widest)


def read(ctx):
    if ctx.trace is None:
        return None
    busy_ns = sum(d for _, _, d in ctx.trace.ops)
    moved = output_bytes(ctx.trace.ops)
    if busy_ns <= 0 or moved <= 0:
        return None
    return 100.0 * moved / ctx.peaks["hbm_bytes_per_s"] / (busy_ns / 1e9)
