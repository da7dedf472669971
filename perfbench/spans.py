"""The serving path's own spans in a reduced trace.

The program opens ``jax.profiler.TraceAnnotation`` spans at its layer
boundaries -- ``fleet.step``, ``engine.admit``, ``engine.decode``,
``engine.suffix_prefill``, ``engine.sync`` around each blocking read of
a device value, and more -- with its counts as their stats, on the
profiler's timeline beside the device ops.  A program without them
leaves none in its trace, and every reader here then finds nothing.
"""

from __future__ import annotations

import trace_reduce


def starting_in(tr, name: str, t0: float, t1: float) -> list:
    """Host spans called ``name`` that start inside [t0, t1]."""
    return [h for h in tr.host if h.name == name and t0 <= h.start <= t1]


def inside(spans: list, outer) -> list:
    """Those of ``spans`` on ``outer``'s thread and within its interval."""
    return [h for h in spans if h.module == outer.module
            and outer.start <= h.start and h.end <= outer.end]


def waits(tr) -> list:
    """Every ``engine.sync`` span: the host waiting on the device."""
    return [h for h in tr.host if h.name == "engine.sync"]


def wait_ns(syncs: list, outer) -> float:
    """Time inside ``outer`` covered by its nested waits (their union)."""
    return sum(e - s for s, e in trace_reduce.merged(
        inside(syncs, outer), outer.start, outer.end))


def next_wait(syncs: list, h):
    """The first wait on ``h``'s thread that starts once ``h`` ends."""
    return min((w for w in syncs if w.module == h.module
                and w.start >= h.end), key=lambda w: w.start, default=None)


def last_wait_end(syncs: list, h) -> float:
    """When the last wait on ``h``'s thread that ended before ``h``
    started ended: the device had then run every program dispatched
    before it.  0 where there was none."""
    return max((w.end for w in syncs if w.module == h.module
                and w.end <= h.start), default=0.0)


def union(intervals: list) -> list:
    """The (start, end) intervals merged where they overlap."""
    out: list = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [tuple(iv) for iv in out]
