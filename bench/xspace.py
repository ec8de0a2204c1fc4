"""The profiler's ``.xplane.pb`` read in full, event metadata included.

``jax.profiler.ProfileData`` gives each event's own stats but not the
stats of its metadata, and a TPU op's ``tf_op`` (its ``op_name``, which
holds the program's named scopes) lives there.  This module declares the
fields of tsl's ``xplane.proto`` that the benchmark reads, builds their
message classes at import, and parses a trace with the protobuf runtime
that is installed (unknown fields are skipped).  Maps are declared as
their wire form, repeated ``(key, value)`` entries.
"""

from __future__ import annotations

from google.protobuf import descriptor_pb2, descriptor_pool, message_factory

__all__ = ["XSpace", "parse", "stat_value"]

_F = descriptor_pb2.FieldDescriptorProto
_I64, _U64, _DBL = _F.TYPE_INT64, _F.TYPE_UINT64, _F.TYPE_DOUBLE
_STR, _BYT, _MSG = _F.TYPE_STRING, _F.TYPE_BYTES, _F.TYPE_MESSAGE

#: message -> [(field, number, type, repeated, message type)]
_FIELDS = {
    "XStat": [("metadata_id", 1, _I64, 0, None),
              ("double_value", 2, _DBL, 0, None),
              ("uint64_value", 3, _U64, 0, None),
              ("int64_value", 4, _I64, 0, None),
              ("str_value", 5, _STR, 0, None),
              ("bytes_value", 6, _BYT, 0, None),
              ("ref_value", 7, _U64, 0, None)],
    "XEvent": [("metadata_id", 1, _I64, 0, None),
               ("offset_ps", 2, _I64, 0, None),
               ("duration_ps", 3, _I64, 0, None),
               ("stats", 4, _MSG, 1, "XStat")],
    "XLine": [("id", 1, _I64, 0, None), ("name", 2, _STR, 0, None),
              ("timestamp_ns", 3, _I64, 0, None),
              ("events", 4, _MSG, 1, "XEvent"),
              ("display_name", 11, _STR, 0, None)],
    "XEventMetadata": [("id", 1, _I64, 0, None), ("name", 2, _STR, 0, None),
                       ("display_name", 4, _STR, 0, None),
                       ("stats", 5, _MSG, 1, "XStat")],
    "XStatMetadata": [("id", 1, _I64, 0, None), ("name", 2, _STR, 0, None)],
    "EventMetadataEntry": [("key", 1, _I64, 0, None),
                           ("value", 2, _MSG, 0, "XEventMetadata")],
    "StatMetadataEntry": [("key", 1, _I64, 0, None),
                          ("value", 2, _MSG, 0, "XStatMetadata")],
    "XPlane": [("id", 1, _I64, 0, None), ("name", 2, _STR, 0, None),
               ("lines", 3, _MSG, 1, "XLine"),
               ("event_metadata", 4, _MSG, 1, "EventMetadataEntry"),
               ("stat_metadata", 5, _MSG, 1, "StatMetadataEntry"),
               ("stats", 6, _MSG, 1, "XStat")],
    "XSpace": [("planes", 1, _MSG, 1, "XPlane")],
}
_PACKAGE = "bench_xspace"


def _build():
    fdp = descriptor_pb2.FileDescriptorProto(
        name=_PACKAGE + ".proto", package=_PACKAGE, syntax="proto3")
    for name, fields in _FIELDS.items():
        m = fdp.message_type.add(name=name)
        for fname, number, typ, repeated, tname in fields:
            f = m.field.add(name=fname, number=number, type=typ,
                            label=_F.LABEL_REPEATED if repeated
                            else _F.LABEL_OPTIONAL)
            if tname:
                f.type_name = f".{_PACKAGE}.{tname}"
    pool = descriptor_pool.DescriptorPool()
    pool.Add(fdp)
    return message_factory.GetMessageClass(
        pool.FindMessageTypeByName(f"{_PACKAGE}.XSpace"))


XSpace = _build()


def parse(path: str):
    """The ``XSpace`` message of one ``.xplane.pb`` file."""
    space = XSpace()
    with open(path, "rb") as f:
        space.ParseFromString(f.read())
    return space


def stat_value(stat, stat_names: dict):
    """The value of an ``XStat``, whichever of its fields is set; a
    ``ref_value`` names a stat metadata entry whose name is the value
    (``stat_names``: the plane's ``{id: name}``)."""
    if stat.ref_value:
        return stat_names.get(stat.ref_value, "")
    for field in ("str_value", "int64_value", "uint64_value",
                  "double_value"):
        v = getattr(stat, field)
        if v:
            return v
    return 0
