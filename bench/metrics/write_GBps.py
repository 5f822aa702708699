"""Writer: column bytes flushed per second the ``write`` span was open
(atomic save, CRC and the journal append)."""


def read(ctx):
    busy = ctx.spans.get("write", 0.0)
    flushed = ctx.counters.get("writer.bytes_flushed", 0.0)
    if busy <= 0 or flushed <= 0:
        return None
    return flushed / busy / 1e9
