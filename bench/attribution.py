"""Attribution of a profile to the program's own layers, on its clock.

Two readings of one ``.xplane.pb`` that ``reduce_trace`` in ``trace.py``
does not make:

* ``read_profile``: the window's leaf device operations, each with the
  named scope (``repro.obs.scopes``) of its HLO ``op_name`` path, and the
  start of the program's ``obs.anchor`` annotation
  (``repro.obs.trace.Tracer.anchor``), which puts the program's span
  timestamps (``clock.now()`` seconds) on the trace's nanoseconds;
* ``idle_split``: the device's idle time in the window, split into the
  part inside a ``dispatch`` span, the part inside a ``queue`` span (a
  request admitted and not yet dispatched) and the rest (nothing
  admitted).

The op-name path comes from the profile itself. On a TPU it is each
device operation's ``tf_op`` stat, kept in the event's metadata, which
``jax.profiler.ProfileData`` does not expose: ``_metadata`` decodes just
that part of the XSpace protobuf. On the CPU the operations carry
``hlo_module``, ``program_id`` and ``hlo_op`` stats instead, and the
path is the instruction's ``op_name`` in the module's ``Hlo Proto``,
which the ``/host:metadata`` plane holds.

``reduce_trace`` does not call this yet; PERF.md's open questions list
what a benchmark change would wire in.
"""
from __future__ import annotations

import dataclasses
import functools
import re
from collections import defaultdict
from typing import Dict, List, Optional, Tuple

from bench.trace import _DEVICE, _leaves, union_ns
from repro.obs.scopes import scope_of
from repro.obs.trace import ANCHOR

__all__ = ["Profile", "read_profile", "scope_ns", "idle_split",
           "intersect"]

_PROGRAM = re.compile(r"\((\d+)\)$")


@dataclasses.dataclass
class Profile:
    """One window of a profile: ``lo``/``hi`` its bounds (ns); per device,
    ``ops`` its leaf operations [(start_ns, duration_ns, scope)] and
    ``busy`` every operation's [(start_ns, end_ns)]; ``anchor_ns`` the
    start of the last ``obs.anchor`` annotation, or None."""
    lo: float
    hi: float
    ops: List[List[Tuple[float, float, str]]]
    busy: List[List[Tuple[float, float]]]
    anchor_ns: Optional[float]

    def ns(self, t: float, anchor_t: float) -> float:
        """A ``clock.now()`` reading ``t`` on the trace's timeline, given
        the reading ``anchor_t`` that ``Tracer.anchor`` took."""
        if self.anchor_ns is None:
            raise ValueError(f"no {ANCHOR!r} annotation in the profile")
        return self.anchor_ns + (t - anchor_t) * 1e9


@functools.lru_cache(maxsize=1)
def _messages():
    """Just the XSpace fields read here (tsl/profiler/protobuf/
    xplane.proto and xla/service/hlo.proto numbering); the parser skips
    the rest."""
    from google.protobuf import (descriptor_pb2, descriptor_pool,
                                 message_factory)
    F = descriptor_pb2.FieldDescriptorProto
    fp = descriptor_pb2.FileDescriptorProto(
        name="bench_xplane_min.proto", package="bench_xplane",
        syntax="proto3")

    def message(name, fields, parent=None):
        m = (parent.nested_type if parent else fp.message_type).add(
            name=name)
        for fname, number, ftype, ref in fields:
            f = m.field.add(name=fname, number=number, type=ftype,
                            label=(F.LABEL_REPEATED if ref and ref[0] == "*"
                                   else F.LABEL_OPTIONAL))
            if ref:
                f.type_name = ".bench_xplane." + ref.lstrip("*")
        return m

    S, B, I64, U64, M = (F.TYPE_STRING, F.TYPE_BYTES, F.TYPE_INT64,
                         F.TYPE_UINT64, F.TYPE_MESSAGE)
    message("XStat", [("metadata_id", 1, I64, None),
                      ("int64_value", 4, I64, None),
                      ("uint64_value", 3, U64, None),
                      ("str_value", 5, S, None),
                      ("bytes_value", 6, B, None),
                      ("ref_value", 7, U64, None)])
    message("XEventMetadata", [("name", 2, S, None),
                               ("stats", 5, M, "*XStat")])
    message("XStatMetadata", [("name", 2, S, None)])
    plane = message("XPlane", [
        ("name", 2, S, None),
        ("event_metadata", 4, M, "*XPlane.EventMetadataEntry"),
        ("stat_metadata", 5, M, "*XPlane.StatMetadataEntry")])
    for entry, value in (("EventMetadataEntry", "XEventMetadata"),
                         ("StatMetadataEntry", "XStatMetadata")):
        e = message(entry, [("key", 1, I64, None), ("value", 2, M, value)],
                    parent=plane)
        e.options.map_entry = True
    message("XSpace", [("planes", 1, M, "*XPlane")])
    message("OpMetadata", [("op_name", 2, S, None)])
    message("Instruction", [("name", 1, S, None),
                            ("metadata", 7, M, "OpMetadata")])
    message("Computation", [("instructions", 2, M, "*Instruction")])
    message("Module", [("computations", 3, M, "*Computation")])
    message("HloProto", [("hlo_module", 1, M, "Module")])
    pool = descriptor_pool.DescriptorPool()
    pool.Add(fp)
    return tuple(message_factory.GetMessageClass(
        pool.FindMessageTypeByName(f"bench_xplane.{n}"))
        for n in ("XSpace", "HloProto"))


def _metadata(path: str):
    """({device plane: {event name: tf_op path}},
    {program id: {instruction: op_name path}}) from the profile."""
    xspace, hlo_proto = _messages()
    with open(path, "rb") as f:
        space = xspace.FromString(f.read())
    tf_ops, programs = {}, {}
    for plane in space.planes:
        stat_names = {k: v.name for k, v in plane.stat_metadata.items()}
        if _DEVICE.match(plane.name):
            tf_ops[plane.name] = {
                m.name: s.str_value or stat_names.get(s.ref_value, "")
                for m in plane.event_metadata.values() for s in m.stats
                if stat_names.get(s.metadata_id) == "tf_op"}
        elif plane.name == "/host:metadata":
            for m in plane.event_metadata.values():
                program = _PROGRAM.search(m.name)
                blob = next((s.bytes_value for s in m.stats
                             if s.bytes_value), b"")
                if program and blob:
                    module = hlo_proto.FromString(blob).hlo_module
                    programs[program.group(1)] = {
                        i.name: i.metadata.op_name
                        for c in module.computations
                        for i in c.instructions}
    return tf_ops, programs


def read_profile(path: str, window: str = "bench.window") -> Profile:
    """The last ``window`` host annotation of the profile at ``path``:
    its device operations with their scopes, and the anchor."""
    from jax.profiler import ProfileData

    tf_ops, programs = _metadata(path)
    on_cpu = not tf_ops                # no device plane: the CPU backend
    devices, cpu_ops, marks, anchors = [], [], [], []
    for plane in ProfileData.from_file(path).planes:
        if _DEVICE.match(plane.name):
            paths = tf_ops.get(plane.name, {})
            devices += [[(e.start_ns, e.duration_ns, paths.get(e.name, ""))
                         for e in line.events]
                        for line in plane.lines if line.name == "XLA Ops"]
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                for e in line.events:
                    if e.name == window:
                        marks.append((e.start_ns, e.start_ns + e.duration_ns))
                    elif e.name == ANCHOR:
                        anchors.append(e.start_ns)
                    elif on_cpu:
                        stats = dict(e.stats)
                        if "hlo_op" in stats:
                            ops = programs.get(str(stats.get("program_id")),
                                               {})
                            cpu_ops.append((e.start_ns, e.duration_ns,
                                            ops.get(stats["hlo_op"], "")))
    if on_cpu and cpu_ops:             # host threads run the programs
        devices = [cpu_ops]
    if not devices:
        raise ValueError(f"no device operations in {path}")
    if not marks:
        raise ValueError(f"no host annotation {window!r} in {path}")
    lo, hi = sorted(marks)[-1]
    ops, busy = [], []
    for events in devices:
        inside = [(s, d, p) for s, d, p in events if s >= lo and s + d <= hi]
        busy.append([(s, s + d) for s, d, _ in inside])
        ops.append([(s, d, scope_of(p)) for s, d, p in _leaves(inside)])
    return Profile(lo=lo, hi=hi, ops=ops, busy=busy,
                   anchor_ns=max(anchors) if anchors else None)


def scope_ns(profile: Profile) -> Dict[str, float]:
    """{scope: leaf device ns in the window}, averaged over devices."""
    out = defaultdict(float)
    for ops in profile.ops:
        for _, d, scope in ops:
            out[scope] += d / len(profile.ops)
    return dict(out)


def _merge(intervals) -> List[Tuple[float, float]]:
    """Sorted, disjoint union of [(start, end)]."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], e))
        elif e > s:
            out.append((s, e))
    return out


def intersect(a, b) -> List[Tuple[float, float]]:
    """Intersection of two unions of intervals, as a disjoint list."""
    a, b, out, i, j = _merge(a), _merge(b), [], 0, 0
    while i < len(a) and j < len(b):
        s, e = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if e > s:
            out.append((s, e))
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return out


def _length(intervals) -> float:
    return sum(e - s for s, e in intervals)


def idle_split(profile: Profile, anchor_t: float, spans) -> dict:
    """The window's device idle ns (averaged over devices), split by the
    program's spans [(name, t_start, t_end)] (``clock.now()`` seconds),
    put on the trace's clock by the anchor: ``dispatch`` (idle inside a
    dispatch), ``queued`` (inside a ``queue`` span and no dispatch) and
    ``rest``. ``dispatches`` counts distinct dispatch intervals: the
    batcher records one identical ``dispatch`` span per request of a
    batch."""
    def on_trace(name):
        return {(profile.ns(t0, anchor_t), profile.ns(t1, anchor_t))
                for n, t0, t1 in spans if n == name}

    dispatch, queued = on_trace("dispatch"), on_trace("queue")
    out = dict.fromkeys(("idle", "dispatch", "queued"), 0.0)
    for busy in profile.busy:
        _, gaps = union_ns(busy, profile.lo, profile.hi)
        in_dispatch = intersect(gaps, dispatch)
        in_queue = intersect(gaps, queued)
        out["idle"] += _length(gaps)
        out["dispatch"] += _length(in_dispatch)
        out["queued"] += (_length(in_queue)
                          - _length(intersect(in_queue, dispatch)))
    out = {k: v / len(profile.busy) for k, v in out.items()}
    out["rest"] = out["idle"] - out["dispatch"] - out["queued"]
    out["dispatches"] = len(dispatch)
    return out
