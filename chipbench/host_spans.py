"""The program's own host spans inside the harness's engine steps.

The program marks the phases of a serving step with profiler spans
(``engine.plan``, ``sched.fill``, ``backend.wait``, ...), on the same clock
as the device's planes; the harness wraps each ``step_once`` in an
``engine.step_once`` span.  The readers of the host-time metrics take the
spans that lie inside each traced ``engine.step_once`` and sum them by
name.

A program that marks no phase at all (one older than the spans) gives no
number: its readers return None.  A program that marks some phases but not
the ones a reader needs stops the run instead, so that a renamed span never
reads as zero host time, or as a step spent wholly on the host.
"""

from __future__ import annotations

import bisect
import sys
from typing import Dict, List, Optional, Sequence, Tuple

STEP = "engine.step_once"
WAIT = "backend.wait"
# the program's span names (the harness's wrappers are named after the
# methods they wrap: engine.step_once, sched.schedule, backend.<method>)
PROGRAM = ("engine.admit", "engine.plan", "engine.account", "engine.gc",
           "sched.refine", "sched.group", "sched.fill",
           "backend.stage", "backend.launch", "backend.wait",
           "backend.unpack")


def host_spans(ctx) -> Optional[List[Tuple]]:
    """The traced window's host spans as (Event, depth): ``ctx["host"]``
    where the harness hands them over; otherwise the list that ``run.py``'s
    ``main`` loaded from the trace (its ``tr_``, of which only the
    reduction reaches ``ctx``), found on the call stack beside this very
    ``ctx``.  None when neither holds them."""
    if "host" in ctx:
        return ctx["host"]
    f = sys._getframe(1)
    while f is not None:
        loc = f.f_locals
        if loc.get("ctx") is ctx and hasattr(loc.get("tr_"), "host"):
            return loc["tr_"].host
        f = f.f_back
    return None


def in_steps(host) -> List[Tuple[object, List[object]]]:
    """Each ``engine.step_once`` span with the program spans inside it."""
    evs = sorted((e for e, _ in host if e.name in PROGRAM),
                 key=lambda e: e.start)
    starts = [e.start for e in evs]
    out = []
    for s in sorted((e for e, _ in host if e.name == STEP),
                    key=lambda e: e.start):
        i = bisect.bisect_left(starts, s.start)
        j = bisect.bisect_right(starts, s.end)
        out.append((s, [e for e in evs[i:j] if e.end <= s.end]))
    return out


def summed_ms(inner, names: Sequence[str]) -> float:
    return sum(e.end - e.start for e in inner if e.name in names) / 1e6


def per_step_ms(ctx, names: Sequence[str], what: str) -> Optional[float]:
    """Summed duration of the spans ``names`` inside the traced steps over
    the number of steps, in milliseconds; None for a program without spans.
    Raises when the program has spans but none of ``names`` lies inside a
    step, or none of its steps waited on the device."""
    steps = _steps(ctx, what)
    if steps is None:
        return None
    if not any(e.name in names for _, inner in steps for e in inner):
        raise ValueError(f"{what}: no span named {sorted(names)} inside the "
                         f"{len(steps)} traced steps; the program's spans "
                         f"there: {_names(steps)}")
    return sum(summed_ms(inner, names) for _, inner in steps) / len(steps)


def _steps(ctx, what: str):
    host = host_spans(ctx)
    if host is None:
        return None
    steps = in_steps(host)
    if not any(inner for _, inner in steps):
        return None
    if not any(e.name == WAIT for _, inner in steps for e in inner):
        raise ValueError(f"{what}: {len(steps)} traced steps and no "
                         f"{WAIT!r} span inside any; the program's spans "
                         f"there: {_names(steps)}")
    return steps


def _names(steps) -> Dict[str, int]:
    out: Dict[str, int] = {}
    for _, inner in steps:
        for e in inner:
            out[e.name] = out.get(e.name, 0) + 1
    return out


def host_ms(ctx, what: str) -> Optional[float]:
    """Mean over the traced steps of the step's duration less the time the
    host spent waiting on the device inside it, in milliseconds."""
    steps = _steps(ctx, what)
    if steps is None:
        return None
    return sum((s.end - s.start) / 1e6 - summed_ms(inner, (WAIT,))
               for s, inner in steps) / len(steps)
