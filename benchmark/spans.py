"""The benchmark's own span recorder: wall-clock intervals taken by the
benchmark's trainer around its calls into each layer.

One process-wide list under a lock; a span is ``(party, name, round,
t_start, t_end)`` on ``time.time()``'s clock (the flight recorder's
clock, so both kinds of span share one timeline).  Round keys are the
trainer's own counter over the whole run (verification rounds first).
"""

from __future__ import annotations

import collections
import threading
import time

Span = collections.namedtuple("Span", "party name round t_start t_end")


class SpanLog:
    def __init__(self) -> None:
        self._lock = threading.Lock()
        self._spans: list = []

    def add(self, party, name, round_, t_start, t_end=None) -> None:
        span = Span(party, name, round_, t_start,
                    time.time() if t_end is None else t_end)
        with self._lock:
            self._spans.append(span)

    def all(self, name=None) -> list:
        with self._lock:
            spans = list(self._spans)
        return [s for s in spans if name is None or s.name == name]


def union_length(intervals) -> float:
    """Total length covered by ``[(start, end), ...]``."""
    total, cur_s, cur_e = 0.0, None, None
    for s, e in sorted(intervals):
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    if cur_e is not None:
        total += cur_e - cur_s
    return total
