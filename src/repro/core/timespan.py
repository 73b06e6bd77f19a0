"""Timespan management (paper §4.5, Fig. 4).

History is divided into non-overlapping timespans holding a roughly equal
number of events (uniform-in-events is the paper's practical choice);
partitioning and slot maps are frozen within a span and rebuilt at
boundaries.
"""
from __future__ import annotations

import dataclasses
from typing import List

from repro.core.events import EventLog


@dataclasses.dataclass
class TimeSpan:
    tsid: int
    t_start: int  # inclusive
    t_end: int  # inclusive
    ev_lo: int  # event index range [lo, hi) in the global log
    ev_hi: int


def split_timespans(events: EventLog, events_per_span: int) -> List[TimeSpan]:
    """Equal-event-count spans; boundaries never split a timestamp (all
    events of one t land in one span, keeping snapshots well-defined)."""
    n = len(events)
    if n == 0:
        return [TimeSpan(0, 0, 0, 0, 0)]
    spans: List[TimeSpan] = []
    lo = 0
    tsid = 0
    while lo < n:
        hi = min(lo + events_per_span, n)
        # extend to include all events with the same timestamp
        if hi < n:
            t_edge = events.t[hi - 1]
            while hi < n and events.t[hi] == t_edge:
                hi += 1
        spans.append(
            TimeSpan(tsid, int(events.t[lo]), int(events.t[hi - 1]), lo, hi)
        )
        tsid += 1
        lo = hi
    return spans

