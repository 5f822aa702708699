"""The order in which a run's window takes shards, and the sequence that
stops handing them out at the deadline.

A plan's shards differ by orders of magnitude in how many chunks they
hold, and the heavy ones sit at its end, so a window that starts at shard
0 measures only the easy head.  ``bit_reversed`` visits shard ``i`` at
the position whose binary digits are ``i``'s reversed: every prefix of
the order spreads evenly over the whole plan, and the order is the same
in every run.
"""
from __future__ import annotations

import time
from typing import Callable, Iterator, List, Optional, Sequence


def bit_reversed(n: int) -> List[int]:
    """``0 .. n-1``, each index at the bit-reversed position."""
    if n <= 0:
        return []
    bits = (n - 1).bit_length()
    order = []
    for i in range(1 << bits):
        r = int(format(i, f"0{bits}b")[::-1], 2) if bits else 0
        if r < n:
            order.append(r)
    return order


ORDERS = {"bit_reversed": bit_reversed}


def shard_order(rule: str, n: int) -> List[int]:
    try:
        return ORDERS[rule](n)
    except KeyError:
        raise ValueError(f"unknown shard_order {rule!r}; "
                         f"known: {sorted(ORDERS)}") from None


class UntilDeadline:
    """The records of ``items`` in order, handed out while the clock is
    before ``deadline`` (``time.perf_counter`` seconds).  ``on_next`` is
    told the position of each record just before it is handed out."""

    def __init__(self, items: Sequence, deadline: float,
                 on_next: Optional[Callable[[int], None]] = None,
                 clock: Callable[[], float] = time.perf_counter):
        self.items = items
        self.deadline = deadline
        self.on_next = on_next
        self.clock = clock
        self.handed_out = 0

    def __len__(self) -> int:
        return len(self.items)

    def __iter__(self) -> Iterator:
        for i, item in enumerate(self.items):
            if self.clock() >= self.deadline:
                break
            if self.on_next is not None:
                self.on_next(i)
            self.handed_out = i + 1
            yield item
