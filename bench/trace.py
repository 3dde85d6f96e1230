"""Reduction of a profiler trace (``.xplane.pb``) to the benchmark's numbers.

The JAX profiler writes one XSpace per traced window.  Device planes are
named ``/device:TPU:<n>``; the line ``XLA Ops`` holds one event per
operation that ran on that chip (fusions, custom calls such as the Pallas
kernels, collectives), named by its HLO text, ``%<instruction> = <shape>
<opcode>(...)``.  Control-flow operations (a scan's ``while``) are events
too and span the events of their bodies.  The reduction keeps the
instruction name alone (``amp_decode_fused.19``).  The benchmark's own
host spans (``jax.profiler.TraceAnnotation``, names starting with
``bench:``) sit on the host plane ``/host:CPU`` on the same clock.

Everything here works on plain tuples ``(start_ns, end_ns, name)`` so that
the tests can feed a small synthetic trace.
"""
from __future__ import annotations

import dataclasses
import glob
import os
import re
from typing import Dict, Iterable, List, Sequence, Tuple

Interval = Tuple[float, float, str]

SPAN_PREFIX = "bench:"
_DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
_OPS_LINE = "XLA Ops"
#: substrings of the names of cross-chip collective operations
COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter",
               "collective-permute", "all-to-all")


@dataclasses.dataclass
class Trace:
    """Device operations per chip and the benchmark's host spans."""
    device_ops: Dict[int, List[Interval]]
    host_spans: List[Interval]
    #: {plane: {line: events}} of the whole trace, for the record
    planes: Dict[str, Dict[str, int]] = dataclasses.field(
        default_factory=dict)

    def span(self, name: str) -> List[Interval]:
        """Every host span called ``bench:<name>``, in time order."""
        full = SPAN_PREFIX + name
        return sorted(s for s in self.host_spans if s[2] == full)

    def window(self) -> Tuple[float, float]:
        """(start_ns, end_ns) of the traced window (the ``bench:window``
        span); raises when the trace holds none."""
        spans = self.span("window")
        if not spans:
            raise ValueError("trace holds no bench:window span")
        return spans[0][0], spans[-1][1]


def load(log_dir: str) -> Trace:
    """Read the one ``.xplane.pb`` the profiler wrote under ``log_dir``."""
    from jax.profiler import ProfileData

    paths = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(paths) != 1:
        raise ValueError(f"expected one .xplane.pb under {log_dir}, "
                         f"found {len(paths)}")
    data = ProfileData.from_file(paths[0])
    device_ops: Dict[int, List[Interval]] = {}
    host: List[Interval] = []
    planes: Dict[str, Dict[str, int]] = {}
    for plane in data.planes:
        planes[plane.name] = {line.name: sum(1 for _ in line.events)
                              for line in plane.lines}
        m = _DEVICE_PLANE.match(plane.name)
        if m:
            ops = device_ops.setdefault(int(m.group(1)), [])
            for line in plane.lines:
                if line.name == _OPS_LINE:
                    ops.extend((e.start_ns, e.start_ns + e.duration_ns,
                                op_name(e.name)) for e in line.events)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host.extend((e.start_ns, e.start_ns + e.duration_ns, e.name)
                            for e in line.events
                            if e.name.startswith(SPAN_PREFIX))
    return Trace(device_ops={k: sorted(v) for k, v in device_ops.items()},
                 host_spans=sorted(host), planes=planes)


def op_name(text: str) -> str:
    """``%name.N = f32[...] opcode(...)`` -> ``name.N``."""
    return text.split(" = ", 1)[0].lstrip("%")


def leaves(intervals: Iterable[Interval]) -> List[Interval]:
    """The events that span no other event: the work itself, without the
    control-flow operations around it."""
    ivs = sorted(intervals, key=lambda iv: (iv[0], -iv[1]))
    return [iv for i, iv in enumerate(ivs)
            if i + 1 == len(ivs) or ivs[i + 1][0] >= iv[1]
            or ivs[i + 1][1] > iv[1]]


def clip(intervals: Iterable[Interval], lo: float, hi: float
         ) -> List[Interval]:
    """The parts of ``intervals`` inside [lo, hi]."""
    out = []
    for s, e, n in intervals:
        s2, e2 = max(s, lo), min(e, hi)
        if e2 > s2:
            out.append((s2, e2, n))
    return out


def union(intervals: Iterable[Interval]) -> List[Tuple[float, float]]:
    """Merged (start, end) pairs covering the intervals."""
    merged: List[List[float]] = []
    for s, e, _ in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return [(s, e) for s, e in merged]


def busy_ns(intervals: Iterable[Interval], lo: float, hi: float) -> float:
    """Length of the union of the intervals inside [lo, hi]."""
    return sum(e - s for s, e in union(clip(intervals, lo, hi)))


def idle_frac(intervals: Iterable[Interval], lo: float, hi: float) -> float:
    """1 - busy / window over [lo, hi]."""
    return 1.0 - busy_ns(intervals, lo, hi) / (hi - lo)


def named(intervals: Iterable[Interval], patterns: Sequence[str]
          ) -> List[Interval]:
    """The events whose name matches any of the regular expressions
    ``patterns``."""
    return [iv for iv in intervals
            if any(re.search(p, iv[2]) for p in patterns)]


def seconds_of(intervals: Iterable[Interval], patterns: Sequence[str],
               lo: float, hi: float) -> float:
    """Summed device time of the events named by ``patterns`` in [lo, hi]
    (patterns are regular expressions)."""
    return sum(e - s for s, e, _ in clip(named(intervals, patterns), lo, hi)
               ) * 1e-9


def exposed_collective_ns(intervals: Iterable[Interval], lo: float,
                          hi: float) -> float:
    """Time in [lo, hi] during which a collective runs and no other
    operation does."""
    ivs = leaves(clip(intervals, lo, hi))
    coll = union(named(ivs, COLLECTIVES))
    compute = union([iv for iv in ivs
                     if not any(re.search(p, iv[2]) for p in COLLECTIVES)])
    overlap, i, j = 0.0, 0, 0
    while i < len(coll) and j < len(compute):
        s = max(coll[i][0], compute[j][0])
        e = min(coll[i][1], compute[j][1])
        overlap += max(e - s, 0.0)
        if coll[i][1] < compute[j][1]:
            i += 1
        else:
            j += 1
    return sum(e - s for s, e in coll) - overlap


def top_ops(trace: Trace, lo: float, hi: float, n: int = 10
            ) -> List[List]:
    """The ``n`` operations (leaves) with the most device time in [lo, hi],
    averaged over the chips: ``[[name, seconds], ...]``."""
    tot: Dict[str, float] = {}
    for ops in trace.device_ops.values():
        for s, e, name in leaves(clip(ops, lo, hi)):
            tot[name] = tot.get(name, 0.0) + (e - s)
    chips = max(len(trace.device_ops), 1)
    ranked = sorted(tot.items(), key=lambda kv: -kv[1])[:n]
    return [[name, ns * 1e-9 / chips] for name, ns in ranked]


def idle_gaps(trace: Trace, lo: float, hi: float, n: int = 10
              ) -> List[List]:
    """The ``n`` longest gaps in chip 0's busy union inside [lo, hi], each
    named by the innermost benchmark span that covers its middle."""
    busy = union(clip(trace.device_ops.get(min(trace.device_ops), []),
                      lo, hi)) if trace.device_ops else []
    gaps, cur = [], lo
    for s, e in busy:
        if s > cur:
            gaps.append((cur, s))
        cur = max(cur, e)
    if hi > cur:
        gaps.append((cur, hi))
    gaps.sort(key=lambda g: g[0] - g[1])
    out = []
    for s, e in gaps[:n]:
        mid = 0.5 * (s + e)
        covering = [sp for sp in trace.host_spans if sp[0] <= mid <= sp[1]]
        name = (min(covering, key=lambda sp: sp[1] - sp[0])[2]
                if covering else "no span")
        out.append([name[len(SPAN_PREFIX):] if name.startswith(SPAN_PREFIX)
                    else name, (e - s) * 1e-9])
    return out


def device_busy_s(trace: Trace, lo: float, hi: float) -> float:
    """Busy seconds in [lo, hi], averaged over the chips in the trace."""
    if not trace.device_ops:
        return 0.0
    return sum(busy_ns(ops, lo, hi) for ops in trace.device_ops.values()
               ) * 1e-9 / len(trace.device_ops)


def summary(trace: Trace, per_chip: int = 5) -> Dict:
    """A small description of the trace for the record: the planes and
    their lines with event counts, the first operation names per chip, and
    the span names."""
    return {
        "planes": trace.planes,
        "chips": {str(k): {"events": len(v),
                           "names": sorted({iv[2] for iv in v})[:per_chip]}
                  for k, v in trace.device_ops.items()},
        "spans": sorted({s[2] for s in trace.host_spans}),
    }
