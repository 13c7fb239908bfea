"""From a profiler trace to device busy time, per study and per chip.

The harness wraps each study, and its prepare / dispatch / wait / fetch
pieces, in ``jax.profiler.TraceAnnotation`` host spans.  The profiler
writes those spans and the device's own events into one ``.xplane.pb``
on one clock.  ``extract`` keeps what the reduction needs:

* per chip the cell uses (a ``/device:TPU:<k>`` plane, ``k`` the JAX
  device id; planes of chips the cell leaves idle are dropped), the
  intervals of its program executions (line ``XLA Modules``): the
  device is busy while one runs;
* per chip, the summed time of each operation (line ``XLA Ops``);
* the host spans, by name.

``Trace`` then gives busy seconds, idle share, busy time inside each
study's span, and the idle gaps by what the host was doing.
"""
from __future__ import annotations

import bisect
import dataclasses
import glob
import itertools
import os
import re
from typing import Dict, List, Sequence, Tuple

SPAN_STUDY = "study"
SPAN_PIECES = ("prepare", "dispatch", "wait", "fetch")
_DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")

Interval = Tuple[float, float]


def merge(intervals: Sequence[Interval]) -> List[Interval]:
    """The union of intervals, as sorted disjoint intervals."""
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def covered(merged: Sequence[Interval], start: float, end: float) -> float:
    """Length of ``[start, end]`` that the merged intervals cover."""
    return sum(max(0.0, min(e, end) - max(s, start)) for s, e in merged)


def gaps(merged: Sequence[Interval], start: float, end: float
         ) -> List[Interval]:
    """The parts of ``[start, end]`` that the merged intervals leave."""
    out, at = [], start
    for s, e in merged:
        if e <= at or s >= end:
            continue
        if s > at:
            out.append((at, s))
        at = max(at, e)
    if at < end:
        out.append((at, end))
    return out


def short_op(name: str) -> str:
    """``%fusion.4 = f32[8] fusion(...), calls=%fc.1`` -> ``fusion.4 (fc.1)``."""
    head = name.split(" = ", 1)[0].lstrip("%")
    calls = re.search(r"calls=%?([\w.\-]+)", name)
    return f"{head} ({calls.group(1)})" if calls else head


@dataclasses.dataclass
class Trace:
    """What the reduction reads of one trace; times in ns, trace clock."""
    chips: List[str]                        # device plane names
    busy: List[List[Interval]]              # per chip, merged executions
    op_ns: List[Dict[str, float]]           # per chip, op name -> ns
    spans: List[Tuple[str, float, float]]   # host spans (name, start, end)

    def studies(self) -> List[Interval]:
        return sorted((s, e) for n, s, e in self.spans if n == SPAN_STUDY)

    def window(self) -> Interval:
        """From the first traced study's start to the last one's end."""
        st = self.studies()
        return (st[0][0], st[-1][1])

    def window_s(self) -> float:
        s, e = self.window()
        return (e - s) * 1e-9

    def busy_s(self) -> List[float]:
        """Seconds each chip executed a program inside the window."""
        s, e = self.window()
        return [covered(b, s, e) * 1e-9 for b in self.busy]

    def study_busy_s(self) -> List[List[float]]:
        """[study][chip] seconds a chip executed inside the study's span."""
        return [[covered(b, s, e) * 1e-9 for b in self.busy]
                for s, e in self.studies()]

    def top_ops(self, n: int = 10) -> List[list]:
        """The ``n`` operations with the most device seconds, chip mean."""
        total: Dict[str, float] = {}
        for ops in self.op_ns:
            for name, ns in ops.items():
                total[name] = total.get(name, 0.0) + ns
        k = max(len(self.op_ns), 1)
        top = sorted(total.items(), key=lambda kv: -kv[1])[:n]
        return [[name, ns * 1e-9 / k] for name, ns in top]

    def idle_by_host(self, n: int = 10) -> List[list]:
        """Idle device seconds (chip mean) by what the host was doing: each
        piece of an idle gap goes to the host span around it (a study
        piece, else ``study``, else ``between studies``); the ``n``
        largest."""
        w0, w1 = self.window()
        pieces = _Sequence([(s, e, name) for name, s, e in self.spans
                            if name in SPAN_PIECES])
        studies = _Sequence([(s, e, SPAN_STUDY) for s, e in self.studies()])
        total: Dict[str, float] = {}
        for b in self.busy:
            for g0, g1 in gaps(b, w0, w1):
                near = pieces.within(g0, g1) + studies.within(g0, g1)
                cuts = sorted({g0, g1} | {x for s, e, _ in near
                                          for x in (s, e) if g0 < x < g1})
                for a, z in zip(cuts, cuts[1:]):
                    mid = 0.5 * (a + z)
                    label = next((name for s, e, name in near
                                  if s <= mid <= e), "between studies")
                    total[label] = total.get(label, 0.0) + (z - a)
        k = max(len(self.busy), 1)
        top = sorted(total.items(), key=lambda kv: -kv[1])[:n]
        return [[label, ns * 1e-9 / k] for label, ns in top]


class _Sequence:
    """Host spans that follow one another without overlap."""

    def __init__(self, spans):
        self.spans = sorted(spans)
        self.ends = [e for _, e, _ in self.spans]

    def within(self, start: float, end: float) -> list:
        """The spans that overlap ``(start, end)``."""
        i = bisect.bisect_right(self.ends, start)
        return list(itertools.takewhile(lambda sp: sp[0] < end,
                                        self.spans[i:]))


def extract(profile, device_ids: Sequence[int]) -> Trace:
    """Reduce a ``jax.profiler.ProfileData`` to a ``Trace`` of the chips
    ``device_ids``."""
    chips, busy, op_ns, spans = [], [], [], []
    names = {SPAN_STUDY, *SPAN_PIECES}
    for plane in profile.planes:
        m = _DEVICE_PLANE.match(plane.name)
        if m and int(m.group(1)) in device_ids:
            runs, ops = [], {}
            for line in plane.lines:
                if line.name == "XLA Modules":
                    runs = [(e.start_ns, e.start_ns + e.duration_ns)
                            for e in line.events]
                elif line.name == "XLA Ops":
                    for e in line.events:
                        key = short_op(e.name)
                        ops[key] = ops.get(key, 0.0) + e.duration_ns
            chips.append((int(m.group(1)), plane.name, merge(runs), ops))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                spans.extend((e.name, e.start_ns, e.start_ns + e.duration_ns)
                             for e in line.events if e.name in names)
    chips.sort()
    return Trace([c[1] for c in chips], [c[2] for c in chips],
                 [c[3] for c in chips], spans)


def load(directory: str, device_ids: Sequence[int]) -> Trace:
    """The ``Trace`` of the chips ``device_ids`` in the one ``.xplane.pb``
    under ``directory``."""
    from jax.profiler import ProfileData
    files = glob.glob(os.path.join(directory, "**", "*.xplane.pb"),
                      recursive=True)
    if len(files) != 1:
        raise RuntimeError(f"expected one trace under {directory}, "
                           f"found {len(files)}")
    return extract(ProfileData.from_file(files[0]), device_ids)
