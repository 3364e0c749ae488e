"""How a driver drives its entry through the window."""

from __future__ import annotations

import sys
import time
from dataclasses import dataclass, field


@dataclass
class Window:
    samples: list = field(default_factory=list)    # seconds per call
    items: int = 0                                  # decided, failed included
    failed: int = 0                                 # in calls that raised
    elapsed: float = 0.0                            # start to the last call's end
    decisions: list = field(default_factory=list)  # (pool index, decision)
    next_k: int = 0


def closed(call, items_per_call: int, seconds: float, k: int) -> Window:
    """One caller, calls back to back until ``seconds`` have passed
    since the first began: every call timed, every decision kept."""
    w = Window()
    start = t1 = time.monotonic()
    while t1 - start < seconds:
        t0 = time.monotonic()
        try:
            w.decisions += call(k)
        except Exception as e:  # noqa: BLE001 — counted, and not correct
            print(f"bench: call {k} raised {e!r}", file=sys.stderr)
            w.failed += items_per_call
        t1 = time.monotonic()
        w.samples.append(t1 - t0)
        w.items += items_per_call
        k += 1
    w.elapsed, w.next_k = t1 - start, k
    return w
