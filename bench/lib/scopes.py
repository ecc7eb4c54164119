"""Split a profile of the round by the program's own names.

The program names each phase of a round with ``jax.named_scope``
(``fed.sample``, ``fed.gather``, ``fed.local_sgd``, ``fed.flatten``,
``fed.aggregate``; docs/observability.md), which XLA keeps in the
``op_name`` of every op the phase produces (a fusion carries its root
instruction's), and writes its host spans (``sched.*``, ``engine.*``,
``svc.*``, ``ckpt.*``) into any active profile.  From a trace read with
``load``:

  scope time  each device op's self time (``trace.self_ns``: a while
              loop is not counted again with its body) summed under the
              op's ``fed.*`` scope, "" for an op outside every phase;
  span idle   device-idle gaps whose midpoint lies inside a program
              span, each summed under the innermost such span.

Both are seconds per chip over the window; divide by the rounds done
for a figure per round.  An op's scope is the innermost ``fed.*`` scope
of its op_name.  Ops the compiler makes after the program is traced (the
pieces of a concatenate, layout copies and pads) carry no op_name at
all; such an op takes the scope of the op it was made for, its nearest
user in the module's dataflow graph that has one (``_module_scopes``).
On the TPU the op_name is the ``tf_op`` stat of the op's event metadata
and the module is the HLO proto of the ``/host:metadata`` plane, both
in the ``.xplane.pb`` (``op_scopes``).  ``bench/lib/trace.py`` keeps
neither an op's scope nor the program's spans, so ``load`` adds them to
what it reads.
"""
from __future__ import annotations

import bisect
import collections
import functools
import glob
import os
import re
from dataclasses import dataclass, field
from typing import Dict, List

from bench.lib import trace as tr

SCOPES = ("fed.sample", "fed.gather", "fed.local_sgd", "fed.flatten",
          "fed.aggregate")
SPAN_PREFIXES = ("sched.", "engine.", "svc.", "ckpt.")
# scopes below the round's that count as one of its phases: the
# weighted_agg kernels' zero pad of their operand finishes the flatten
ALIASES = {"weighted_agg.pad": "fed.flatten"}
_SCOPE = re.compile("(?:%s)(?![A-Za-z0-9_])" % "|".join(
    map(re.escape, SCOPES + tuple(ALIASES))))
# instructions a scope is not carried through: they join unrelated values
# (a shared constant, a loop's parameter or its tuple of carried state)
_BARRIERS = (b"constant", b"parameter", b"tuple")


@dataclass
class Op(tr.Event):
    scope: str = ""


@dataclass
class ScopedTrace(tr.Trace):
    spans: List[tr.Event] = field(default_factory=list)   # program spans


def scope_of(op_name: str) -> str:
    """The phase named innermost in an op_name ("" if none):
    "jit(chunk)/while/body/fed.aggregate/jit(_apply_flat)/fed.flatten/pad"
    -> "fed.flatten"."""
    found = _SCOPE.findall(op_name)
    return ALIASES.get(found[-1], found[-1]) if found else ""


@functools.lru_cache(maxsize=None)
def _classes():
    """The message classes of the subsets of the profiler's XSpace proto
    (tsl/profiler/protobuf/xplane.proto: a plane's name, its event and
    stat metadata, and each stat's value) and of XLA's HloProto
    (xla/service/hlo.proto: each instruction's name, opcode, op_name, id
    and operands) read here.  Every other field is skipped on parsing;
    strings are read as bytes, so one that is not UTF-8 cannot fail the
    parse."""
    from google.protobuf import descriptor_pb2, descriptor_pool
    from google.protobuf import message_factory

    F = descriptor_pb2.FieldDescriptorProto
    scalar = {"int64": F.TYPE_INT64, "uint64": F.TYPE_UINT64,
              "bytes": F.TYPE_BYTES}
    f = descriptor_pb2.FileDescriptorProto(
        name="bench_profile_subset.proto", package="bench_profile",
        syntax="proto3")

    def message(name, *fields):
        """fields: (name, number, kind); a kind is a scalar type or a
        message name, repeated if it ends in "*"."""
        m = f.message_type.add(name=name)
        for field_name, number, kind in fields:
            fd = m.field.add(name=field_name, number=number,
                             label=F.LABEL_OPTIONAL)
            base = kind.rstrip("*")
            if kind.endswith("*"):
                fd.label = F.LABEL_REPEATED
            if base in scalar:
                fd.type = scalar[base]
            else:
                fd.type = F.TYPE_MESSAGE
                fd.type_name = ".bench_profile." + base

    message("XStat", ("metadata_id", 1, "int64"),
            ("uint64_value", 3, "uint64"), ("int64_value", 4, "int64"),
            ("str_value", 5, "bytes"), ("bytes_value", 6, "bytes"))
    message("XEventMetadata", ("name", 2, "bytes"), ("stats", 5, "XStat*"))
    message("XStatMetadata", ("name", 2, "bytes"))
    # map<int64, V> fields are, on the wire, repeated {key = 1, value = 2}
    message("EventMetadataEntry", ("key", 1, "int64"),
            ("value", 2, "XEventMetadata"))
    message("StatMetadataEntry", ("key", 1, "int64"),
            ("value", 2, "XStatMetadata"))
    message("XPlane", ("name", 2, "bytes"),
            ("event_metadata", 4, "EventMetadataEntry*"),
            ("stat_metadata", 5, "StatMetadataEntry*"))
    message("XSpace", ("planes", 1, "XPlane*"))
    message("OpMetadata", ("op_name", 2, "bytes"))
    message("HloInstruction", ("name", 1, "bytes"), ("opcode", 2, "bytes"),
            ("metadata", 7, "OpMetadata"), ("id", 35, "int64"),
            ("operand_ids", 36, "int64*"))
    message("HloComputation", ("instructions", 2, "HloInstruction*"))
    message("HloModule", ("computations", 3, "HloComputation*"))
    message("HloProto", ("hlo_module", 1, "HloModule"))
    pool = descriptor_pool.DescriptorPool()
    pool.Add(f)
    return tuple(message_factory.GetMessageClass(
        pool.FindMessageTypeByName("bench_profile." + n))
        for n in ("XSpace", "HloProto"))


def _module_scopes(hlo_proto: bytes) -> Dict[str, str]:
    """Each instruction's scope in one HLO module, by instruction name.
    An instruction with no op_name was made by the compiler for the
    instruction that uses its result (a layout copy, a piece of a
    concatenate, a pad), so it takes the scope of its nearest user that
    has a phase in its op_name, found through other instructions with no
    op_name; one with no such user takes its nearest producer's.  Ties go
    to the instruction met first in the module's order, and no scope is
    carried through a constant, parameter or tuple."""
    proto = _classes()[1]()
    proto.ParseFromString(hlo_proto)
    instrs = [i for c in proto.hlo_module.computations
              for i in c.instructions]
    operands = {i.id: list(i.operand_ids) for i in instrs}
    users = collections.defaultdict(list)
    for i in instrs:
        for o in i.operand_ids:
            users[o].append(i.id)
    scope = {i.id: scope_of(i.metadata.op_name.decode(errors="replace"))
             for i in instrs}
    open_ = {i.id for i in instrs
             if not i.metadata.op_name and i.opcode not in _BARRIERS}
    for edges in (operands, users):         # to producers, then consumers
        queue = collections.deque(i.id for i in instrs if scope[i.id])
        while queue:
            x = queue.popleft()
            for y in edges.get(x, ()):
                if y in open_ and not scope[y]:
                    scope[y] = scope[x]
                    queue.append(y)
    return {i.name.decode(errors="replace"): scope[i.id] for i in instrs}


def _u64(x: int) -> int:
    return x & (2**64 - 1)


def op_scopes(path: str) -> Dict[str, str]:
    """Each device op's scope (``scope_of`` its op_name, or as
    ``_module_scopes`` gives it for an op with none), keyed by the op's
    event name (its HLO text).  The op_name is the ``tf_op`` stat of the
    op's event metadata and its module the ``program_id`` stat, which
    ``jax.profiler.ProfileData`` does not show, so both are read from the
    file."""
    space = _classes()[0]()
    with open(path, "rb") as f:
        space.ParseFromString(f.read())
    modules: Dict[int, bytes] = {}
    for plane in space.planes:
        if plane.name == b"/host:metadata":
            hlo = {e.key for e in plane.stat_metadata
                   if e.value.name == b"Hlo Proto"}
            modules.update((_u64(e.key), st.bytes_value)
                           for e in plane.event_metadata
                           for st in e.value.stats if st.metadata_id in hlo)
    module_scopes = {}
    out: Dict[str, str] = {}
    for plane in space.planes:
        if not plane.name.startswith(b"/device:"):
            continue
        ids = {e.value.name: e.key for e in plane.stat_metadata}
        for e in plane.event_metadata:
            stats = {st.metadata_id: st for st in e.value.stats}
            name = e.value.name.decode(errors="replace")
            tf_op = stats.get(ids.get(b"tf_op"))
            if tf_op is not None and tf_op.str_value:
                out[name] = scope_of(tf_op.str_value.decode(
                    errors="replace"))
                continue
            prog = stats.get(ids.get(b"program_id"))
            if prog is None:
                continue
            key = _u64(prog.uint64_value or prog.int64_value)
            if key in modules and key not in module_scopes:
                module_scopes[key] = _module_scopes(modules[key])
            out[name] = module_scopes.get(key, {}).get(
                tr.short(name).lstrip("%"), "")
    return out


def load(trace_dir: str) -> ScopedTrace:
    """``trace.load``, with each device op's scope (``op_scopes``) and
    the program's host spans as well."""
    from jax.profiler import ProfileData

    base = tr.load(trace_dir)
    # the file trace.load read
    path = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                            recursive=True), key=os.path.getmtime)[-1]
    scope = op_scopes(path)
    devices = {d: [Op(e.name, e.start_ns, e.dur_ns, scope.get(e.name, ""))
                   for e in ops] for d, ops in base.devices.items()}
    spans = [tr.Event(e.name, float(e.start_ns), float(e.duration_ns))
             for plane in ProfileData.from_file(path).planes
             if plane.name.startswith("/host:")
             for line in plane.lines for e in line.events
             if e.name.startswith(SPAN_PREFIXES)]
    return ScopedTrace(devices=devices, host=base.host, spans=spans)


def _per_chip(totals: Dict[str, float], n_dev: int) -> Dict[str, float]:
    return {k: v / max(1, n_dev) / 1e9 for k, v in totals.items()}


def scope_seconds(t: tr.Trace, lo: float, hi: float) -> Dict[str, float]:
    """Device self seconds per chip under each scope ("" for ops with
    none), for the ops that start inside [lo, hi]."""
    out: Dict[str, float] = {}
    for ops in t.devices.values():
        for o, s in tr.self_ns(ops):
            if lo <= o.start_ns <= hi:
                k = getattr(o, "scope", "")
                out[k] = out.get(k, 0.0) + s
    return _per_chip(out, len(t.devices))


def unscoped_ops(t: tr.Trace, lo: float, hi: float,
                 n: int = 10) -> List[list]:
    """The ``n`` ops with no scope that took most self time, as [short
    name, seconds per chip]."""
    by_name: Dict[str, float] = {}
    for ops in t.devices.values():
        for o, s in tr.self_ns(ops):
            if lo <= o.start_ns <= hi and not getattr(o, "scope", ""):
                k = tr.short(o.name)
                by_name[k] = by_name.get(k, 0.0) + s
    top = sorted(_per_chip(by_name, len(t.devices)).items(),
                 key=lambda kv: -kv[1])[:n]
    return [[k, v] for k, v in top]


def _innermost(spans: List[tr.Event]):
    """A function of a time: the name of the shortest span covering it,
    or None.  The span boundaries cut the time line into segments, each
    labelled once."""
    cuts = sorted({x for e in spans for x in (e.start_ns, e.end_ns)})
    names = []
    for a, b in zip(cuts, cuts[1:]):
        mid = (a + b) / 2
        cover = [e for e in spans if e.start_ns <= mid <= e.end_ns]
        names.append(min(cover, key=lambda e: e.dur_ns).name
                     if cover else None)

    def at(x: float):
        i = bisect.bisect_right(cuts, x) - 1
        return names[i] if 0 <= i < len(names) else None
    return at


def span_idle_seconds(t: ScopedTrace, lo: float,
                      hi: float) -> Dict[str, float]:
    """Device-idle seconds per chip in gaps whose midpoint lies inside a
    program span, keyed by the innermost span there.  Gaps outside every
    program span (the harness's own work, say) are not counted."""
    at = _innermost(t.spans)
    out: Dict[str, float] = {}
    for ops in t.devices.values():
        for s, e in tr.idle_gaps(ops, lo, hi):
            name = at((s + e) / 2)
            if name is not None:
                out[name] = out.get(name, 0.0) + (e - s)
    return _per_chip(out, len(t.devices))


def report(t: ScopedTrace, lo: float, hi: float, rounds: int) -> dict:
    """Per round, in ms: device self time under each scope and in total
    busy, the unscoped remainder by op, and idle in each program span."""
    per_round = 1e3 / max(1, rounds)
    by_scope = scope_seconds(t, lo, hi)
    idle = span_idle_seconds(t, lo, hi)
    busy = tr.mean_busy_s(t, lo, hi)
    return {
        "rounds": rounds,
        "window_ms": (hi - lo) / 1e6,
        "busy_ms_per_round": busy * per_round,
        "scope_ms_per_round": {k: by_scope.get(k, 0.0) * per_round
                               for k in SCOPES},
        "unscoped_ms_per_round": by_scope.get("", 0.0) * per_round,
        "scoped_share_of_busy": (sum(by_scope.get(k, 0.0) for k in SCOPES)
                                 / busy if busy else 0.0),
        "unscoped_ops_ms_per_round": [[k, v * per_round] for k, v in
                                      unscoped_ops(t, lo, hi)],
        "program_idle_ms_per_round": sum(idle.values()) * per_round,
        "idle_ms_per_round_by_span": {k: v * per_round for k, v in
                                      sorted(idle.items())},
    }
