"""From a profiler trace to what the metric readers read.

The harness wraps each study, and its prepare / dispatch / wait / fetch
pieces, in ``jax.profiler.TraceAnnotation`` host spans; the program may
write host spans of its own and run its passes under named scopes
(``jax.named_scope``).  The profiler writes all of it and the device's own
events into one ``.xplane.pb`` on one clock.  ``load`` reads that file
once, and ``extract`` keeps what the readers need:

* per chip the cell uses (a ``/device:TPU:<k>`` plane, ``k`` the JAX
  device id; planes of chips the cell leaves idle are dropped), the
  intervals of its program executions (line ``XLA Modules``): the
  device is busy while one runs;
* per chip, the summed time of each operation (line ``XLA Ops``);
* per chip and traced study, the device time of the leaf operations
  (all but the ``while``, ``conditional`` and ``call`` containers, whose
  events span their bodies' operations) that start inside the study, by
  the operation's name path (``jit(f)/while/body/<scope>/...``, read
  by ``op_paths``);
* the harness's host spans, and every other host span by name.

``Trace`` then gives busy seconds, idle share, busy time inside each
study's span, the idle gaps by what the host was doing, and two readings
that take their names from the caller: ``scope_ms`` and ``idle_ms``.
Nothing here names a span or a scope of the program; the metric files
(``metrics/<name>.py``) name what they read.
"""
from __future__ import annotations

import bisect
import dataclasses
import functools
import glob
import itertools
import os
import re
import statistics
from typing import Dict, List, Optional, Sequence, Tuple

SPAN_STUDY = "study"
SPAN_PIECES = ("prepare", "dispatch", "wait", "fetch")
_DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
# the stat of an operation's event metadata that holds its name path
OP_PATH_STAT = "tf_op"
# operations whose trace event spans the operations of their bodies
CONTAINERS = ("while", "conditional", "call")
_TRANSFORM = re.compile(r"^[\w\-]+\((.*)\)$")

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


def opcode(name: str) -> str:
    """``%while.3 = (s32[], f32[8]{0}) while(...), ...`` -> ``while``."""
    rest = name.split(" = ", 1)[-1]
    if rest.startswith("("):                # a tuple shape: skip to its end
        depth = 0
        for i, ch in enumerate(rest):
            depth += (ch == "(") - (ch == ")")
            if depth == 0:
                rest = rest[i + 1:]
                break
    else:
        rest = rest.split(" ", 1)[-1]
    return rest.strip().split("(", 1)[0]


@functools.lru_cache(maxsize=1 << 16)
def scopes(path: str) -> Tuple[str, ...]:
    """The components of an operation's name path, outermost first, each
    taken out of its transforms: ``jit(f)/while/vmap(scope_a)/add`` ->
    ``('f', 'while', 'scope_a', 'add')``."""
    out = []
    for part in path.split("/") if path else ():
        while _TRANSFORM.match(part):
            part = _TRANSFORM.match(part).group(1)
        out.append(part)
    return tuple(out)


@dataclasses.dataclass
class Trace:
    """What the readers read of one trace; times in ns, trace clock."""
    chips: List[str]                        # device plane names
    busy: List[List[Interval]]              # per chip, merged executions
    op_ns: List[Dict[str, float]]           # per chip, op name -> ns
    spans: List[Tuple[str, float, float]]   # the harness's host spans
    # every other host span (the program's among them), by name
    host: Dict[str, List[Interval]] = dataclasses.field(default_factory=dict)
    # [chip][traced study] leaf operations' name path -> ns
    leaf_ns: List[List[Dict[str, float]]] = dataclasses.field(
        default_factory=list)

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

    def scope_ms(self, name: str) -> Optional[float]:
        """Device ms per traced study of the leaf operations whose name
        path has the scope ``name`` as a component (under any transform,
        ``vmap(name)`` included), the busiest chip; the median over the
        traced studies.  None where no operation ran under ``name``."""
        per_study = [max((sum(ns for path, ns in chip[k].items()
                              if name in scopes(path))
                          for chip in self.leaf_ns), default=0.0)
                     for k in range(len(self.studies()))]
        if not any(per_study):
            return None
        return 1e-6 * statistics.median(per_study)

    def idle_ms(self, names: Sequence[str]) -> Optional[float]:
        """Device-idle ms per traced study inside the host spans
        ``names``: the parts of the study in which the host was inside
        one of them and the chip ran no program, chip mean; the median
        over the traced studies.  None where no such span was traced."""
        inside = merge([iv for n in names for iv in self.host.get(n, ())])
        if not inside or not self.busy:
            return None
        per_study = []
        for s, e in self.studies():
            clipped = [(max(a, s), min(z, e)) for a, z in inside
                       if a < e and z > s]
            idle = [sum(z - a - covered(b, a, z) for a, z in clipped)
                    for b in self.busy]
            per_study.append(sum(idle) / len(idle))
        return 1e-6 * statistics.median(per_study)

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


def extract(profile, device_ids: Sequence[int],
            paths: Optional[Dict[str, Dict[str, str]]] = None) -> Trace:
    """Reduce a ``jax.profiler.ProfileData`` to a ``Trace`` of the chips
    ``device_ids``; ``paths`` (``op_paths``) gives each operation's name
    path, per device plane."""
    spans, host = [], {}
    harness = {SPAN_STUDY, *SPAN_PIECES}
    for plane in profile.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    iv = (e.start_ns, e.start_ns + e.duration_ns)
                    if e.name in harness:
                        spans.append((e.name, *iv))
                    else:
                        host.setdefault(e.name, []).append(iv)
    studies = sorted((s, e) for n, s, e in spans if n == SPAN_STUDY)
    starts = [s for s, _ in studies]
    chips = []
    for plane in profile.planes:
        m = _DEVICE_PLANE.match(plane.name)
        if not (m and int(m.group(1)) in device_ids):
            continue
        named = (paths or {}).get(plane.name, {})
        runs, ops = [], {}
        leaves: List[Dict[str, float]] = [{} for _ in studies]
        info = {}           # op name -> (short name, name path of a leaf)
        for line in plane.lines:
            if line.name == "XLA Modules":
                runs = [(e.start_ns, e.start_ns + e.duration_ns)
                        for e in line.events]
            elif line.name == "XLA Ops":
                for e in line.events:
                    name, t, ns = e.name, e.start_ns, e.duration_ns
                    if name not in info:
                        leaf = opcode(name) not in CONTAINERS
                        info[name] = (short_op(name),
                                      named.get(name, "") if leaf else None)
                    key, path = info[name]
                    ops[key] = ops.get(key, 0.0) + ns
                    k = bisect.bisect_right(starts, t) - 1
                    if path is not None and k >= 0 and t < studies[k][1]:
                        leaves[k][path] = leaves[k].get(path, 0.0) + ns
        chips.append((int(m.group(1)), plane.name, merge(runs), ops, leaves))
    chips.sort(key=lambda c: c[0])
    return Trace([c[1] for c in chips], [c[2] for c in chips],
                 [c[3] for c in chips], spans, host,
                 [c[4] for c in chips])


def _varint(buf, i: int) -> Tuple[int, int]:
    out = shift = 0
    while True:
        byte = buf[i]
        i += 1
        out |= (byte & 0x7F) << shift
        if byte < 0x80:
            return out, i
        shift += 7


def _fields(buf):
    """(field number, value) of a protobuf message, in wire order: an int
    for a varint, the bytes of a length-delimited field (fixed-width
    fields are skipped)."""
    i = 0
    while i < len(buf):
        key, i = _varint(buf, i)
        kind = key & 7
        if kind == 0:
            value, i = _varint(buf, i)
        elif kind == 2:
            n, i = _varint(buf, i)
            value, i = buf[i:i + n], i + n
        elif kind in (1, 5):
            i += 8 if kind == 1 else 4
            continue
        else:
            raise ValueError(f"protobuf wire type {kind} at byte {i}")
        yield key >> 3, value


def op_paths(xspace: bytes) -> Dict[str, Dict[str, str]]:
    """Per device plane, each operation's name -> its framework name path
    (``jit(f)/while/body/<scope>/...``), read from the serialized
    ``XSpace``.  The path is the ``tf_op`` stat of the operation's event
    metadata, which ``ProfileData`` does not expose."""
    out = {}
    for field, plane in _fields(memoryview(xspace)):
        if field != 1:                              # XSpace.planes
            continue
        name, metadata, stat_names = "", [], {}
        for f, v in _fields(plane):
            if f == 2:                              # XPlane.name
                name = bytes(v).decode()
            elif f == 4:                            # XPlane.event_metadata
                metadata.append(v)
            elif f == 5:                            # XPlane.stat_metadata
                entry = dict(_fields(v)).get(2, b"")
                stat = dict(_fields(entry))
                stat_names[stat.get(1, 0)] = bytes(stat.get(2, b"")).decode()
        if not _DEVICE_PLANE.match(name):
            continue
        wanted = {k for k, n in stat_names.items() if n == OP_PATH_STAT}
        paths = out.setdefault(name, {})
        for entry in metadata:
            event = dict(_fields(entry)).get(2, b"")   # XEventMetadata
            op, path = "", ""
            for f, v in _fields(event):
                if f == 2:                          # .name
                    op = bytes(v).decode()
                elif f == 5:                        # .stats
                    stat = dict(_fields(v))
                    if stat.get(1) not in wanted:
                        continue
                    if 5 in stat:                   # XStat.str_value
                        path = bytes(stat[5]).decode()
                    elif 7 in stat:                 # XStat.ref_value
                        path = stat_names.get(stat[7], "")
            paths[op] = path.rstrip(":")
    return out


def load_file(path: str, device_ids: Sequence[int]) -> Trace:
    """The ``Trace`` of the chips ``device_ids`` in one ``.xplane.pb``,
    read once."""
    from jax.profiler import ProfileData
    with open(path, "rb") as f:
        xspace = f.read()
    return extract(ProfileData.from_serialized_xspace(xspace), device_ids,
                   op_paths(xspace))


def trace_file(directory: str) -> str:
    """The one ``.xplane.pb`` under ``directory``."""
    files = glob.glob(os.path.join(directory, "**", "*.xplane.pb"),
                      recursive=True)
    if len(files) != 1:
        raise RuntimeError(f"expected one trace under {directory}, "
                           f"found {len(files)}")
    return files[0]


def load(directory: str, device_ids: Sequence[int]) -> Trace:
    """The ``Trace`` of the chips ``device_ids`` in the one ``.xplane.pb``
    under ``directory``."""
    return load_file(trace_file(directory), device_ids)
