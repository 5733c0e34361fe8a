"""Device time by the scopes the program names its work with.

The program puts the layer boundaries of its two hot steps under
`jax.named_scope`s (`paddle_tpu/profiler/scopes.py`). A scope changes
nothing but an operation's `op_name`, the path XLA keeps in each
instruction's metadata (`jit(decode_block)/while/body/closed_call/mlp/
dot_general`), and the profiler hands that path on with every operation
of a device's `XLA Ops` line, as the `tf_op` stat of the event's
metadata. `load` takes events and paths from an `.xplane.pb`;
everything else works on plain lists, so it can be tested on hand-made
ones.

`SCOPES` is the benchmark's own copy of the program's lists: the
benchmark is the yardstick and reads a program that may lack them (the
parent of the PR that brought them), and then finds nothing.
"""
from __future__ import annotations

import collections
import re

from . import trace as tr

SERVE_SCOPES = ("embed", "attn_qkv", "kv_write", "paged_attention",
                "prefill_attention", "attn_out", "mlp", "lm_head",
                "sampling")
TRAIN_SCOPES = ("embed", "attention", "ffn", "mlm_head_loss", "grad_reduce",
                "optimizer_update")
SCOPES = frozenset(SERVE_SCOPES + TRAIN_SCOPES)

# the stat of an operation's metadata that holds its `op_name` in the
# v5e trace of PR 27, as `<op_name>:<op type>` with the type left empty
OP_NAME_STAT = "tf_op"

ScopedOp = collections.namedtuple(
    "ScopedOp", "plane name start duration op_name")

# a transformation wraps the part of the path it was applied to:
# `transpose(jvp(attention))` is the backward of what ran under
# `attention`
_WRAPPED = re.compile(r"(?:jvp|transpose|checkpoint|vmap)\((.*)\)\Z")


def _xspace():
    """A message class for the part of the profiler's `XSpace` that is
    read here (tsl/profiler/protobuf/xplane.proto, by field number).
    `jax.profiler.ProfileData` shows an event's own stats and not its
    metadata's, where the `op_name` is; the only compiled copy of the
    schema in this installation lies inside tensorflow, which a process
    that holds the chip had better not import. Maps are read as the
    repeated entries they are on the wire; strings as bytes."""
    from google.protobuf import (descriptor_pb2, descriptor_pool,
                                 message_factory)

    field = descriptor_pb2.FieldDescriptorProto
    kinds = {"int64": field.TYPE_INT64, "uint64": field.TYPE_UINT64,
             "bytes": field.TYPE_BYTES}
    schema = {
        "XSpace": [("planes", 1, "XPlane*")],
        "XPlane": [("name", 2, "bytes"), ("lines", 3, "XLine*"),
                   ("event_metadata", 4, "EventMetadataEntry*"),
                   ("stat_metadata", 5, "StatMetadataEntry*")],
        "EventMetadataEntry": [("key", 1, "int64"),
                               ("value", 2, "XEventMetadata")],
        "StatMetadataEntry": [("key", 1, "int64"),
                              ("value", 2, "XStatMetadata")],
        "XLine": [("name", 2, "bytes"), ("timestamp_ns", 3, "int64"),
                  ("events", 4, "XEvent*")],
        "XEvent": [("metadata_id", 1, "int64"), ("offset_ps", 2, "int64"),
                   ("duration_ps", 3, "int64")],
        "XEventMetadata": [("name", 2, "bytes"), ("stats", 5, "XStat*")],
        "XStatMetadata": [("name", 2, "bytes")],
        "XStat": [("metadata_id", 1, "int64"), ("str_value", 5, "bytes"),
                  ("ref_value", 7, "uint64")],
    }
    package = "chipbench.xplane"
    proto = descriptor_pb2.FileDescriptorProto(
        name="chipbench/xplane_subset.proto", package=package,
        syntax="proto3")
    for message, fields in schema.items():
        m = proto.message_type.add(name=message)
        for name, number, kind in fields:
            f = m.field.add(name=name, number=number,
                            label=field.LABEL_OPTIONAL)
            if kind.endswith("*"):
                f.label, kind = field.LABEL_REPEATED, kind[:-1]
            if kind in kinds:
                f.type = kinds[kind]
            else:
                f.type, f.type_name = (field.TYPE_MESSAGE,
                                       f".{package}.{kind}")
    pool = descriptor_pool.DescriptorPool()
    pool.Add(proto)
    return message_factory.GetMessageClass(
        pool.FindMessageTypeByName(f"{package}.XSpace"))


def _text(raw: bytes) -> str:
    return raw.decode("utf-8", "replace")


def load(path: str) -> list:
    """The `XLA Ops` events of every device plane of an `.xplane.pb`,
    each with its `op_name` ('' where the trace gives it none), in
    seconds on the trace's own clock as `trace.load` has them."""
    space = _xspace()()
    with open(path, "rb") as f:
        space.ParseFromString(f.read())
    out = []
    for plane in space.planes:
        plane_name = _text(plane.name)
        if not plane_name.startswith(tr.DEVICE_PLANE):
            continue
        stat_names = {e.key: _text(e.value.name)
                      for e in plane.stat_metadata}
        wanted = {k for k, name in stat_names.items()
                  if name == OP_NAME_STAT}
        names, paths = {}, {}
        for e in plane.event_metadata:
            names[e.key] = _text(e.value.name)
            for s in e.value.stats:
                if s.metadata_id in wanted:
                    # a string, or a reference to one among the stat names
                    value = (_text(s.str_value) if s.str_value
                             else stat_names.get(s.ref_value, ""))
                    paths[e.key] = value.rsplit(":", 1)[0]
        for line in plane.lines:
            if _text(line.name) != tr.OPS_LINE:
                continue
            t0 = line.timestamp_ns * 1e-9
            for ev in line.events:
                out.append(ScopedOp(
                    plane_name, names.get(ev.metadata_id, ""),
                    t0 + ev.offset_ps * 1e-12, ev.duration_ps * 1e-12,
                    paths.get(ev.metadata_id, "")))
    return out


def scopes_of(op_name: str) -> set:
    """The scopes of `SCOPES` that are components of the path, bare or
    inside `jvp(...)`, `transpose(...)`, `checkpoint(...)`."""
    found = set()
    for part in op_name.split("/"):
        while True:
            inner = _WRAPPED.match(part)
            if inner is None:
                break
            part = inner.group(1)
        if part in SCOPES:
            found.add(part)
    return found


def self_seconds(ops) -> list:
    """Each operation's own seconds, without the operations nested
    inside it, in the order of `ops`: `trace.self_times`' rule, asked
    once per event instead of once per name."""
    out = [0.0] * len(ops)
    by_plane = collections.defaultdict(list)
    for i, op in enumerate(ops):
        by_plane[op.plane].append(
            tr.Event(op.plane, tr.OPS_LINE, str(i), op.start, op.duration))
    for events in by_plane.values():
        for i, seconds in tr.self_times(events).items():
            out[int(i)] = seconds
    return out


def share(ops, scope: str, exclude: str = None):
    """Per cent of the device's self time spent in operations under
    `scope`, less those whose own name holds `exclude`; with `scope`
    empty, in operations under no scope of `SCOPES` at all. None where
    no operation carries a path, or none of them a scope of `SCOPES`:
    the program then names nothing and there is nothing to read."""
    own = self_seconds(ops)
    total = sum(own)
    found = [scopes_of(op.op_name) for op in ops]
    if total <= 0 or not any(found):
        return None
    if scope:
        took = sum(s for op, s, f in zip(ops, own, found) if scope in f
                   and not (exclude and exclude in tr.own_name(op.name)))
    else:
        took = sum(s for s, f in zip(own, found) if not f)
    return 100.0 * took / total
