"""Reduce a JAX profiler trace (``.xplane.pb``) to the benchmark's numbers.

Device planes (``/device:TPU:<n>``) give the busy intervals: every event on
the per-operation line ("XLA Ops"), and the program each belongs to from the
per-program line ("XLA Modules").  Host planes give the harness's
``TraceAnnotation`` spans, by which each idle gap on the device is labelled
with what the host was doing in it.  Everything is in nanoseconds on the
trace's clock.
"""

from __future__ import annotations

import bisect
import dataclasses
import glob
import os
import re
from typing import Dict, List, Optional, Sequence, Tuple

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
# JAX numbers each compiled program: "jit_foo(123)" -> "jit_foo"
_MODULE_ID = re.compile(r"\(\d+\)$")
PALLAS = " [pallas]"


@dataclasses.dataclass
class Event:
    name: str
    start: int               # ns
    end: int                 # ns


@dataclasses.dataclass
class Trace:
    devices: Dict[str, List[Event]]        # device plane -> op events
    modules: Dict[str, List[Event]]        # device plane -> program events
    #                                        (named "jit_foo(<id>)")
    host: List[Tuple[Event, int]]          # (annotation span, nesting depth)


def find_xplane(trace_dir: str) -> str:
    hits = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                            recursive=True))
    if not hits:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return hits[-1]


def load(path: str, host_names: Optional[Sequence[str]] = None) -> Trace:
    """Read the trace.  ``host_names``: prefixes of the host spans to keep
    (all host events when None)."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    devices, modules, host = {}, {}, []
    for plane in pd.planes:
        if plane.name.startswith("/device:") and "CPU" not in plane.name:
            for line in plane.lines:
                evs = [Event(e.name, int(e.start_ns),
                             int(e.start_ns + e.duration_ns))
                       for e in line.events]
                if line.name == OPS_LINE:
                    devices[plane.name] = evs
                elif line.name == MODULES_LINE:
                    modules[plane.name] = evs
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                stack: List[int] = []
                for e in sorted(line.events, key=lambda e: (e.start_ns,
                                                            -e.duration_ns)):
                    if host_names is not None and not any(
                            e.name.startswith(p) for p in host_names):
                        continue
                    ev = Event(e.name, int(e.start_ns),
                               int(e.start_ns + e.duration_ns))
                    while stack and stack[-1] <= ev.start:
                        stack.pop()
                    host.append((ev, len(stack)))
                    stack.append(ev.end)
    return Trace(devices, modules, host)


def program_name(module: str) -> str:
    """A program's name without the number JAX gives each compiled
    program: "jit_foo(123)" -> "jit_foo"."""
    return _MODULE_ID.sub("", module)


def clip(evs: List[Event], t0: int, t1: int) -> List[Event]:
    return [Event(e.name, max(e.start, t0), min(e.end, t1)) for e in evs
            if e.end > t0 and e.start < t1]


def union(evs: List[Event]) -> List[Tuple[int, int]]:
    """Merged busy intervals."""
    out: List[List[int]] = []
    for e in sorted(evs, key=lambda e: e.start):
        if out and e.start <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e.end)
        else:
            out.append([e.start, e.end])
    return [(a, b) for a, b in out]


def busy_ns(evs: List[Event]) -> int:
    return sum(b - a for a, b in union(evs))


def idle_gaps(evs: List[Event], t0: int, t1: int) -> List[Tuple[int, int]]:
    """Intervals of [t0, t1] in which no operation ran."""
    gaps, cur = [], t0
    for a, b in union(clip(evs, t0, t1)):
        if a > cur:
            gaps.append((cur, a))
        cur = max(cur, b)
    if cur < t1:
        gaps.append((cur, t1))
    return gaps


def by_name(evs: List[Event]) -> Dict[str, int]:
    """Summed duration per event name (ns)."""
    out: Dict[str, int] = {}
    for e in evs:
        out[e.name] = out.get(e.name, 0) + (e.end - e.start)
    return out


def label_gap(host: List[Tuple[Event, int]], a: int, b: int) -> str:
    """The innermost host span that covers most of the gap [a, b]."""
    best, key = "host (no span)", (0, -1)
    for ev, depth in host:
        if ev.end <= a or ev.start >= b:
            continue
        ov = min(ev.end, b) - max(ev.start, a)
        k = (ov * 2 > (b - a), depth if ov * 2 > (b - a) else ov)
        if k > key:
            best, key = ev.name, k
    return best


def short(op: str) -> str:
    """An operation's instruction name from its HLO text, tagged
    ``[pallas]`` for a compiled Pallas kernel (a TPU custom call)."""
    name = op.split(" = ")[0].lstrip("%")
    return name + (PALLAS if "tpu_custom_call" in op else "")


def leaves(evs: List[Event]) -> List[Event]:
    """Operations that contain no other operation (a while loop or a call
    spans the operations of its body, which are listed on their own)."""
    evs = sorted(evs, key=lambda e: (e.start, -e.end))
    return [e for e, nxt in zip(evs, evs[1:] + [None])
            if nxt is None or nxt.start >= e.end]


def reduce(tr: Trace, t0: int, t1: int, top: int = 10) -> Dict:
    """Busy and idle time over [t0, t1], averaged over the device planes;
    device time and executions per program; device time per operation
    (leaf operations, as "program/instruction"); the longest idle gaps,
    labelled by host span."""
    planes = sorted(tr.devices)
    if not planes:
        raise ValueError("the trace holds no device operations")
    busy = [busy_ns(clip(tr.devices[p], t0, t1)) for p in planes]
    p0 = planes[0]
    compiled = sorted(clip(tr.modules.get(p0, []), t0, t1),
                      key=lambda m: m.start)
    mods = [Event(program_name(m.name), m.start, m.end) for m in compiled]
    starts = [m.start for m in mods]
    op_ns: Dict[str, int] = {}
    kernels: Dict[str, set] = {m.name: set() for m in compiled}
    for e in leaves(clip(tr.devices[p0], t0, t1)):
        mid = (e.start + e.end) // 2
        i = bisect.bisect_right(starts, mid) - 1
        mod = mods[i].name if i >= 0 and mods[i].end >= mid else "?"
        key = f"{mod}/{short(e.name)}"
        op_ns[key] = op_ns.get(key, 0) + e.end - e.start
        if mod != "?" and key.endswith(PALLAS):
            kernels[compiled[i].name].add(short(e.name))
    gaps = sorted(idle_gaps(tr.devices[p0], t0, t1),
                  key=lambda g: g[0] - g[1])[:top]
    op_time = sorted(op_ns.items(), key=lambda kv: -kv[1])
    return dict(
        busy_s=sum(busy) / len(busy) / 1e9, window_s=(t1 - t0) / 1e9,
        program_s={k: v / 1e9 for k, v in by_name(mods).items()},
        program_n={k: sum(1 for m in mods if m.name == k)
                   for k in {m.name for m in mods}},
        op_s={k: v / 1e9 for k, v in op_time},
        program_kernels={k: sorted(v) for k, v in kernels.items()},
        device_ops=[[k, v / 1e9] for k, v in op_time[:top]],
        idle_gaps=[[label_gap(tr.host, a, b), (b - a) / 1e9]
                   for a, b in gaps])


def programs_matching(red: Dict, pat: str) -> Dict[str, List[str]]:
    """Compiled programs (with their numbers) whose name matches ``pat``,
    each with the Pallas kernels it ran."""
    rx = re.compile(pat)
    return {m: ks for m, ks in red["program_kernels"].items()
            if rx.search(program_name(m))}


def seconds_matching(red: Dict, key: str, pat: str) -> Optional[float]:
    """Summed seconds of ``red[key]`` entries whose name matches ``pat``;
    None when nothing matches."""
    rx = re.compile(pat)
    hits = [v for k, v in red[key].items() if rx.search(k)]
    return sum(hits) if hits else None


def check_runs(red: Dict, pat: str, expected: int, what: str) -> None:
    """Raise unless the programs whose name matches ``pat`` ran
    ``expected`` times in the window, give or take the programs that its
    edges cut: a reader that matched another program, or none, would
    measure something else."""
    rx = re.compile(pat)
    got = sum(n for k, n in red["program_n"].items() if rx.search(k))
    if abs(got - expected) > max(2, expected // 100):
        raise ValueError(f"{what}: programs matching {pat!r} ran {got} "
                         f"times in the traced window where the harness "
                         f"dispatched {expected}; programs run: "
                         f"{sorted(red['program_n'])}")
