"""Reduction of a profiler trace to device busy time, kernel time and
exposed collective time.

A trace is first flattened to plain ``Event`` records (``load_xplane``),
so the reduction below is plain Python over (start, duration) intervals
and can be checked on a small recorded trace without a chip.
"""
from __future__ import annotations

import glob
import re
from dataclasses import dataclass, field
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

# Lines of a TPU device plane that hold one event per executed operation
# and per executed program.
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_PLANE = "/host:CPU"
KEEP_STATS = ("hlo_op", "hlo_module", "hlo_category", "long_name", "tf_op")
STAT_CHARS = 400


@dataclass
class Event:
    plane: str
    line: str
    name: str
    start_ns: float
    dur_ns: float
    stats: Dict[str, str] = field(default_factory=dict)

    @property
    def end_ns(self) -> float:
        return self.start_ns + self.dur_ns


def load_xplane(trace_dir: str) -> List[Event]:
    """The events of the newest ``*.xplane.pb`` under ``trace_dir``: every
    device plane's, and the benchmark's own ``bench.*`` host spans
    (``TraceAnnotation``), on whichever host line they landed."""
    import jax
    paths = sorted(glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    pd = jax.profiler.ProfileData.from_file(paths[-1])
    out = []
    for plane in pd.planes:
        dev = plane.name.startswith("/device:")
        host = plane.name.startswith("/host:")
        for line in plane.lines:
            if not (dev or host):
                continue
            for e in line.events:
                if host and not e.name.startswith("bench."):
                    continue
                stats = {}
                if dev:
                    for k, v in e.stats:
                        if k in KEEP_STATS:
                            stats[k] = str(v)[:STAT_CHARS]
                out.append(Event(HOST_PLANE if host else plane.name,
                                 line.name, e.name,
                                 float(e.start_ns), float(e.duration_ns),
                                 stats))
    return out


def device_planes(events: Iterable[Event]) -> List[str]:
    """Device plane names in chip order (``/device:TPU:0`` first)."""
    names = {e.plane for e in events if e.plane.startswith("/device:")}

    def order(n):
        m = re.search(r"(\d+)$", n)
        return (int(m.group(1)) if m else 0, n)

    return sorted(names, key=order)


def select(events: Iterable[Event], plane: Optional[str] = None,
           line: Optional[str] = None,
           name: Optional[str] = None) -> List[Event]:
    """Events on ``plane``/``line`` whose name matches regex ``name``."""
    rx = re.compile(name) if name else None
    return [e for e in events
            if (plane is None or e.plane == plane)
            and (line is None or e.line == line)
            and (rx is None or rx.search(e.name))]


def clip(intervals: Iterable[Tuple[float, float]], t0: float,
         t1: float) -> List[Tuple[float, float]]:
    out = []
    for a, b in intervals:
        a, b = max(a, t0), min(b, t1)
        if b > a:
            out.append((a, b))
    return out


def union(intervals: Iterable[Tuple[float, float]]) -> List[Tuple[float,
                                                                  float]]:
    """Sorted, disjoint union of intervals."""
    out: List[List[float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def length(intervals: Iterable[Tuple[float, float]]) -> float:
    return sum(b - a for a, b in intervals)


def busy_ns(ops: Iterable[Event], t0: float, t1: float) -> float:
    """Nanoseconds of [t0, t1] in which at least one op runs."""
    return length(union(clip(((e.start_ns, e.end_ns) for e in ops),
                             t0, t1)))


def gaps(ops: Iterable[Event], t0: float, t1: float):
    """Intervals of [t0, t1] in which no op runs."""
    busy = union(clip(((e.start_ns, e.end_ns) for e in ops), t0, t1))
    out, cur = [], t0
    for a, b in busy:
        if a > cur:
            out.append((cur, a))
        cur = max(cur, b)
    if t1 > cur:
        out.append((cur, t1))
    return out


def exposed_ns(coll: Iterable[Event], compute: Iterable[Event], t0: float,
               t1: float) -> float:
    """Nanoseconds of [t0, t1] inside a collective during which no
    compute op runs: the collective time not hidden behind compute."""
    c = union(clip(((e.start_ns, e.end_ns) for e in coll), t0, t1))
    busy = union(clip(((e.start_ns, e.end_ns) for e in compute), t0, t1))
    hidden = 0.0
    j = 0
    for a, b in c:
        while j < len(busy) and busy[j][1] <= a:
            j += 1
        k = j
        while k < len(busy) and busy[k][0] < b:
            hidden += min(b, busy[k][1]) - max(a, busy[k][0])
            k += 1
    return length(c) - hidden


def short_name(name: str) -> str:
    """``%fusion.12 = bf16[4,8]{...} fusion(...)`` -> ``fusion.12
    bf16[4,8]``: an HLO op's name and result type, as the device planes
    name their events."""
    m = re.match(r"%?([^ ]+) = (\(?[a-z0-9]+\[[0-9,]*\])", name)
    return f"{m.group(1)} {m.group(2)}" if m else name[:80]


def result_dims(name: str):
    """Dimensions of an HLO op's (first) result, or None."""
    m = re.match(r"%?[^ ]+ = \(?[a-z0-9]+\[([0-9,]*)\]", name)
    if not m:
        return None
    return tuple(int(d) for d in m.group(1).split(",") if d)


def self_times(ops: Sequence[Event], t0: float, t1: float):
    """[(event, ns of [t0, t1] it ran and no event nested in it did)]:
    an event that encloses others (a loop, a called computation) keeps
    only its own time."""
    evs = sorted(ops, key=lambda e: (e.start_ns, -e.dur_ns))
    out, stack = [], []
    for e in evs:
        while stack and stack[-1][0].end_ns <= e.start_ns:
            out.append(tuple(stack.pop()))
        own = length(clip([(e.start_ns, e.end_ns)], t0, t1))
        if stack and e.end_ns <= stack[-1][0].end_ns:
            stack[-1][1] -= own
        stack.append([e, own])
    out.extend(tuple(x) for x in reversed(stack))
    return out


def op_totals(ops: Iterable[Event], t0: float, t1: float, top: int = 10):
    """[[op, seconds], ...] of the ops with most device self time."""
    tot: Dict[str, float] = {}
    for e, own in self_times(list(ops), t0, t1):
        if own > 0:
            n = short_name(e.name)
            tot[n] = tot.get(n, 0.0) + own
    ranked = sorted(tot.items(), key=lambda kv: -kv[1])[:top]
    return [[n, s * 1e-9] for n, s in ranked]


def is_kernel(e: Event) -> bool:
    """A Pallas (Mosaic) kernel launch."""
    return 'custom_call_target="tpu_custom_call"' in e.name


def attribute_gaps(gap_list, host_spans: Sequence[Event], top: int = 10):
    """[[host span name, seconds], ...]: each idle gap's time given to the
    innermost host span that covers it (``idle`` where none does)."""
    tot: Dict[str, float] = {}
    spans = sorted(host_spans, key=lambda e: e.start_ns)
    for a, b in gap_list:
        for lo, hi in _split_by_spans(a, b, spans):
            name = _innermost(spans, lo, hi)
            tot[name] = tot.get(name, 0.0) + (hi - lo)
    ranked = sorted(tot.items(), key=lambda kv: -kv[1])[:top]
    return [[n, s * 1e-9] for n, s in ranked]


def _split_by_spans(a, b, spans):
    cuts = {a, b}
    for s in spans:
        for t in (s.start_ns, s.end_ns):
            if a < t < b:
                cuts.add(t)
    pts = sorted(cuts)
    return list(zip(pts[:-1], pts[1:]))


def _innermost(spans, lo, hi) -> str:
    best = None
    for s in spans:
        if s.start_ns <= lo and s.end_ns >= hi:
            if best is None or s.dur_ns < best.dur_ns:
                best = s
    return best.name if best is not None else "idle"
