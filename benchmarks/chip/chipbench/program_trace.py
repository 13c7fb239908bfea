"""The program's own spans and pass scopes in a profiler trace.

``chipbench.tracing`` reduces a trace to what the harness reads: the
harness's study spans, program executions and operation times.  The
program also traces itself, and this module reads that part:

* the program's host spans (names that start with ``repro.``:
  ``repro.run``, ``repro.grid``, ``repro.summarize`` and their pieces),
  which nest inside the harness's ``dispatch`` and ``fetch`` pieces;
* per chip, the device time of the leaf operations (all but the
  ``while``, ``conditional`` and ``call`` containers, whose events span
  their bodies' operations) that start inside a traced study, by the
  pass scope (``jax.named_scope`` in ``engine.step`` and
  ``engine.batched_run``) named in the operation's own metadata, or
  ``other``.

``ProgramTrace.readings`` turns them, with the program's loop counters
(``RunStats.iterations``), into ``runner_idle_ms``, ``summary_idle_ms``,
``provision_device_ms`` and ``loop_iter_us``.  ``record_trace.py`` prints
them; the harness does not read them.
"""
from __future__ import annotations

import dataclasses
import re
from typing import Dict, List, Optional, Sequence, Tuple

from chipbench import tracing

PROGRAM_PREFIX = "repro."
SPANS_RUNNER = ("repro.run", "repro.grid")
SPAN_SUMMARY = "repro.summarize"
# the pass scopes of engine.step and engine.batched_run, and the bucket of
# operations outside all of them
PASSES = ("events", "autoscaler", "provision", "phases", "rates",
          "migration", "flows", "horizon", "commit", "probes", "leap",
          "record", "freeze")
OTHER = "other"
# the stat of an operation's event metadata that holds its name path
OP_PATH_STAT = "tf_op"
# operations whose trace event spans the operations of their bodies
CONTAINERS = ("while", "conditional", "call")

_TRANSFORM = re.compile(r"^[\w\-]+\((.*)\)$")


def scope_of(op_path: str) -> str:
    """The innermost pass scope in an operation's name path
    (``jit(_run)/while/body/provision/...`` -> ``provision``); under a
    transform the scope reads ``vmap(provision)``."""
    for part in reversed(op_path.split("/")):
        while _TRANSFORM.match(part):
            part = _TRANSFORM.match(part).group(1)
        if part in PASSES:
            return part
    return OTHER


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
    (``jit(_run)/while/body/provision/...``), read from the serialized
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
        if not tracing._DEVICE_PLANE.match(name):
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


@dataclasses.dataclass
class ProgramTrace:
    """The harness's ``Trace`` of a run and what the program recorded in
    it; times in ns, trace clock."""
    trace: tracing.Trace
    spans: List[Tuple[str, float, float]]   # ``repro.*`` (name, start, end)
    pass_ns: List[Dict[str, float]]         # per chip, pass scope -> ns

    def idle_s(self, names: Sequence[str]) -> List[float]:
        """Per chip, seconds of the window in which the chip ran no
        program while the host was inside a program span named in
        ``names``."""
        w0, w1 = self.trace.window()
        inside = tracing.merge([(max(s, w0), min(e, w1))
                                for n, s, e in self.spans
                                if n in names and s < w1 and e > w0])
        return [sum(e - s for a, z in inside
                    for s, e in tracing.gaps(b, a, z)) * 1e-9
                for b in self.trace.busy]

    def pass_ms(self) -> Dict[str, float]:
        """Device ms per traced study of each pass scope, and ``other``,
        the busiest chip for each."""
        n = len(self.trace.studies())
        out: Dict[str, float] = {}
        for chip in self.pass_ns:
            for k, ns in chip.items():
                out[k] = max(out.get(k, 0.0), 1e-6 * ns / n)
        return out

    def _idle_ms(self, names: Sequence[str]) -> Optional[float]:
        """Device-idle ms per traced study inside the spans ``names``,
        chip mean; None where the program wrote no such span."""
        if not any(n in names for n, _, _ in self.spans):
            return None
        idle = self.idle_s(names)
        return 1e3 * sum(idle) / len(idle) / len(self.trace.studies())

    def readings(self, iterations: Sequence[Optional[int]] = ()
                 ) -> Dict[str, Optional[float]]:
        """The program's per-layer readings of the traced studies, None
        where the trace (or ``iterations``, each traced study's loop
        trips) holds nothing to read:

        * ``runner_idle_ms``: device idle inside ``repro.run`` /
          ``repro.grid`` per study, chip mean;
        * ``summary_idle_ms``: device idle inside ``repro.summarize`` per
          study, chip mean;
        * ``provision_device_ms``: leaf operations under the
          ``provision`` scope per study, busiest chip;
        * ``loop_iter_us``: each study's device time (busiest chip) over
          its loop trips, mean over the studies."""
        if not self.trace.chips or not self.trace.studies():
            return dict.fromkeys(("runner_idle_ms", "summary_idle_ms",
                                  "provision_device_ms", "loop_iter_us"))
        passes = self.pass_ms()
        per_study = self.trace.study_busy_s()
        trips = list(iterations)[:len(per_study)]
        loop = (1e6 * sum(max(chips) / t for chips, t
                          in zip(per_study, trips)) / len(trips)
                if len(trips) == len(per_study) and all(trips) else None)
        return {"runner_idle_ms": self._idle_ms(SPANS_RUNNER),
                "summary_idle_ms": self._idle_ms((SPAN_SUMMARY,)),
                "provision_device_ms": passes.get("provision"),
                "loop_iter_us": loop}


def extract(profile, device_ids: Sequence[int],
            paths: Optional[Dict[str, Dict[str, str]]] = None
            ) -> ProgramTrace:
    """Reduce a ``jax.profiler.ProfileData`` to the ``ProgramTrace`` of
    the chips ``device_ids``; ``paths`` (``op_paths``) names each
    operation's pass scope."""
    trace = tracing.extract(profile, device_ids)
    studies = tracing._Sequence([(s, e, tracing.SPAN_STUDY)
                                 for s, e in trace.studies()])
    chips, spans = [], []
    for plane in profile.planes:
        m = tracing._DEVICE_PLANE.match(plane.name)
        if m and int(m.group(1)) in device_ids:
            named = (paths or {}).get(plane.name, {})
            passes: Dict[str, float] = {}
            for line in plane.lines:
                if line.name != "XLA Ops":
                    continue
                for e in line.events:
                    if (opcode(e.name) not in CONTAINERS
                            and studies.within(e.start_ns, e.start_ns + 1)):
                        k = scope_of(named.get(e.name, ""))
                        passes[k] = passes.get(k, 0.0) + e.duration_ns
            chips.append((int(m.group(1)), passes))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                spans.extend((e.name, e.start_ns, e.start_ns + e.duration_ns)
                             for e in line.events
                             if e.name.startswith(PROGRAM_PREFIX))
    chips.sort(key=lambda c: c[0])
    return ProgramTrace(trace, spans, [c[1] for c in chips])


def load_file(path: str, device_ids: Sequence[int]) -> ProgramTrace:
    """The ``ProgramTrace`` of the chips ``device_ids`` in one
    ``.xplane.pb``."""
    from jax.profiler import ProfileData
    with open(path, "rb") as f:
        xspace = f.read()
    return extract(ProfileData.from_serialized_xspace(xspace), device_ids,
                   op_paths(xspace))
