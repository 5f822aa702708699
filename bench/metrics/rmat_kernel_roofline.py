"""R-MAT kernel (``pallas_prng``): the least time its events in the
traced slice could take at the chip's HBM peak, over the time they took
on the device.  The bytes are those of the arrays each event's HLO text
names: the id words the kernel writes, and the block seeds and the θ
table it reads.  It draws its random bits inside the chip, so its
arithmetic is not counted: no VPU peak is published for the chip, and
this reads at or below the true roofline share.

The kernel's ``pallas_call`` carries no ``name=``, so the trace names its
op only by its HLO: a Mosaic custom call, the one Pallas kernel this
cell runs."""
from bench import trace_reduce

KERNEL = 'custom_call_target="tpu_custom_call"'


def read(ctx):
    if ctx.trace is None:
        return None
    ops = [e for e in ctx.trace.ops if KERNEL in e[0]]
    busy_ns = sum(d for _, _, d in ops)
    moved = sum(trace_reduce.hlo_bytes(name) for name, _, _ in ops)
    if busy_ns <= 0 or moved <= 0:
        return None
    return 100.0 * moved / ctx.peaks["hbm_bytes_per_s"] / (busy_ns / 1e9)
