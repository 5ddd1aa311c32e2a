"""Device time by named scope, from a profiler trace (``.xplane.pb``).

    python3 -m bench.scopes <trace directory or .xplane.pb> [--window NAME]

The engine opens ``jax.named_scope``s in its device programs (``gsi.draft``,
``gsi.verify/prm``, ``gsi.fallback``, ``gsi.admit/gsi.commit/target``, ...:
``docs/SERVING.md``).  A scope changes only the HLO ``op_name`` metadata,
and the TPU trace carries it as the ``tf_op`` stat of each operation's
event metadata (``jit(_decode_core)/gsi.draft/while/body/dot_general``).
``jax.profiler.ProfileData``, which :mod:`bench.trace` reads, does not
expose event-metadata stats, so the XSpace protocol buffer is read here
through a minimal descriptor of its messages, built with the ``protobuf``
package.

As in :mod:`bench.trace`, operations count where they start inside the
``window`` span, on every TPU device, and control-flow wrappers are left
out: by name, and also where only the metadata's ``hlo_category`` names
them (``%cond.3.clone = ... conditional(...)``).  An operation without
the stat has the path ``""``.

The command prints one JSON object: for each program in the window, its
executions, its device time and its operations' time per execution, the
time per execution under each scope of :data:`SCOPES` that holds any, and
``covered``, the share of the operations' time under some ``gsi.*`` scope.
"""
from __future__ import annotations

import argparse
import json
import os
import re
import sys
from collections import defaultdict
from dataclasses import dataclass
from typing import Dict, List, Tuple

from bench import trace

#: the engine's scopes, and their children that the report lists
SCOPES = ("gsi.draft", "gsi.verify", "gsi.verify/prm", "gsi.verify/target",
          "gsi.select", "gsi.fallback", "gsi.commit", "gsi.commit/draft",
          "gsi.commit/target", "gsi.commit/prm", "gsi.admit", "gsi.extend",
          "gsi.target")
PROGRAM_ID = re.compile(r"\((\d+)\)$")


@dataclass
class Scopes:
    #: program -> scope path -> [seconds, operations] (all devices)
    paths: Dict[str, Dict[str, List[float]]]

    def time(self, program: str, scope: str) -> float:
        """Seconds of ``program``'s operations under ``scope``: one named
        scope (``gsi.draft``) or a run of nested ones
        (``gsi.admit/gsi.commit/draft``), anywhere in an operation's path."""
        needle = f"/{scope}/"
        return sum(t for path, (t, _) in self.paths.get(program, {}).items()
                   if needle in f"/{path}/")

    def under(self, program: str, prefix: str = "gsi.") -> float:
        """Seconds of ``program``'s operations under any scope whose name
        starts with ``prefix``."""
        return sum(t for path, (t, _) in self.paths.get(program, {}).items()
                   if any(p.startswith(prefix) for p in path.split("/")))

    def total(self, program: str) -> float:
        return sum(t for t, _ in self.paths.get(program, {}).values())


def _xspace_class():
    """The XSpace message class, from a minimal descriptor of the
    profiler's ``xplane.proto`` (the fields read here and their numbers)."""
    from google.protobuf import descriptor_pb2, descriptor_pool
    from google.protobuf import message_factory
    F = descriptor_pb2.FieldDescriptorProto
    scalar = {"i": F.TYPE_INT64, "u": F.TYPE_UINT64, "d": F.TYPE_DOUBLE,
              "s": F.TYPE_STRING, "b": F.TYPE_BYTES}
    # message -> (field, number, type: a scalar code or a message name,
    # repeated)
    messages = {
        "XStat": [("metadata_id", 1, "i", 0), ("double_value", 2, "d", 0),
                  ("uint64_value", 3, "u", 0), ("int64_value", 4, "i", 0),
                  ("str_value", 5, "s", 0), ("bytes_value", 6, "b", 0),
                  ("ref_value", 7, "u", 0)],
        "XEvent": [("metadata_id", 1, "i", 0), ("offset_ps", 2, "i", 0),
                   ("duration_ps", 3, "i", 0), ("stats", 4, "XStat", 1)],
        "XLine": [("id", 1, "i", 0), ("name", 2, "s", 0),
                  ("timestamp_ns", 3, "i", 0), ("events", 4, "XEvent", 1)],
        "XEventMetadata": [("id", 1, "i", 0), ("name", 2, "s", 0),
                           ("stats", 5, "XStat", 1)],
        "XStatMetadata": [("id", 1, "i", 0), ("name", 2, "s", 0)],
        "XPlane": [("id", 1, "i", 0), ("name", 2, "s", 0),
                   ("lines", 3, "XLine", 1),
                   ("event_metadata", 4, "XPlane.EventMetadataEntry", 1),
                   ("stat_metadata", 5, "XPlane.StatMetadataEntry", 1)],
        "XSpace": [("planes", 1, "XPlane", 1)],
    }
    fd = descriptor_pb2.FileDescriptorProto(
        name="bench_xplane.proto", package="bench_xplane", syntax="proto3")

    def add_fields(msg, fields):
        for fname, num, typ, rep in fields:
            f = msg.field.add(name=fname, number=num, label=(
                F.LABEL_REPEATED if rep else F.LABEL_OPTIONAL))
            if typ in scalar:
                f.type = scalar[typ]
            else:
                f.type, f.type_name = F.TYPE_MESSAGE, f".bench_xplane.{typ}"

    for name, fields in messages.items():
        msg = fd.message_type.add(name=name)
        add_fields(msg, fields)
        if name == "XPlane":
            for entry, value in (("EventMetadataEntry", "XEventMetadata"),
                                 ("StatMetadataEntry", "XStatMetadata")):
                e = msg.nested_type.add(name=entry)
                e.options.map_entry = True
                add_fields(e, [("key", 1, "i", 0), ("value", 2, value, 0)])
    pool = descriptor_pool.DescriptorPool()
    pool.Add(fd)
    return message_factory.GetMessageClass(
        pool.FindMessageTypeByName("bench_xplane.XSpace"))


def _window(space, name: str) -> Tuple[float, float]:
    """The first ``name`` span on a host plane (ns)."""
    for plane in space.planes:
        if not plane.name.startswith("/host:"):
            continue
        ids = {k for k, v in plane.event_metadata.items() if v.name == name}
        for ln in plane.lines:
            for e in ln.events:
                if e.metadata_id in ids:
                    lo = ln.timestamp_ns + e.offset_ps * 1e-3
                    return lo, lo + e.duration_ps * 1e-3
    raise ValueError(f"the trace holds no {name!r} span")


def read(path: str, *, window: str = "window") -> Scopes:
    """Device time of the operations that start in the window, by program
    and scope path (module doc)."""
    space = _xspace_class()()
    with open(path, "rb") as f:
        space.ParseFromString(f.read())
    lo, hi = _window(space, window)
    out: Dict[str, Dict[str, List[float]]] = defaultdict(
        lambda: defaultdict(lambda: [0.0, 0]))
    for plane in space.planes:
        if not trace.DEVICE.match(plane.name):
            continue
        stat_names = {k: v.name for k, v in plane.stat_metadata.items()}
        meta = dict(plane.event_metadata.items())
        lines = {ln.name: ln for ln in plane.lines}
        programs = {}                      # program id -> program name
        if "XLA Modules" in lines:
            for e in lines["XLA Modules"].events:
                name = meta[e.metadata_id].name
                m = PROGRAM_ID.search(name)
                if m:
                    programs[int(m.group(1))] = trace.SUFFIX.sub("", name)
        ops = lines.get("XLA Ops")
        if ops is None:
            continue
        ops_meta = {}                      # metadata id -> (program, path)
        for e in ops.events:
            start = ops.timestamp_ns + e.offset_ps * 1e-3
            if not lo <= start < hi:
                continue
            if e.metadata_id not in ops_meta:
                m = meta[e.metadata_id]
                stats = {stat_names.get(s.metadata_id): s for s in m.stats}
                skip = trace.INSTANCE.sub("", trace.op_name(m.name)) \
                    in trace.CONTROL_FLOW or _str(
                        stats.get("hlo_category"), stat_names) \
                    in trace.CONTROL_FLOW
                ops_meta[e.metadata_id] = None if skip else (
                    programs.get(_int(stats.get("program_id")), "?"),
                    _str(stats.get("tf_op"), stat_names).rstrip(":"))
            key = ops_meta[e.metadata_id]
            if key is not None:
                acc = out[key[0]][key[1]]
                acc[0] += e.duration_ps * 1e-12
                acc[1] += 1
    return Scopes({p: {k: list(v) for k, v in d.items()}
                   for p, d in out.items()})


def _int(stat) -> int:
    return -1 if stat is None else int(stat.int64_value
                                       or stat.uint64_value)


def _str(stat, stat_names) -> str:
    """A string stat's value; a ``ref_value`` names a stat metadata entry
    whose name is the string."""
    if stat is None:
        return ""
    if stat.ref_value:
        return stat_names.get(stat.ref_value, "")
    return stat.str_value


def report(path: str, *, window: str = "window",
           scopes=SCOPES) -> dict:
    """The command's JSON object (module doc); times in ms per
    execution."""
    summary = trace.reduce(path, window=window)
    found = read(path, window=window)
    out = {}
    for program in sorted(found.paths):
        s, n = summary.program_time([program])
        if not n:
            continue
        ops = found.total(program)
        row = {"executions": n, "program_ms": 1e3 * s / n,
               "ops_ms": 1e3 * ops / n,
               "covered": 100.0 * found.under(program) / ops if ops else None,
               "scopes_ms": {}}
        for scope in scopes:
            t = found.time(program, scope)
            if t:
                row["scopes_ms"][scope] = 1e3 * t / n
        out[program] = row
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("trace", help="an .xplane.pb or a directory holding one")
    ap.add_argument("--window", default="window",
                    help="the host span that bounds the counted operations")
    args = ap.parse_args(argv)
    path = trace.find(args.trace) if os.path.isdir(args.trace) \
        else args.trace
    print(json.dumps(report(path, window=args.window), indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
