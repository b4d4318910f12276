"""The profiler's ``.xplane.pb`` schema (tsl/profiler/protobuf/xplane.proto),
declared here so that the trace is read with nothing but ``protobuf``: JAX's
own ``ProfileData`` reader hides the per-op metadata (the ``tf_op`` scope path
among them) that the reduction needs."""

from __future__ import annotations

from google.protobuf import descriptor_pb2, descriptor_pool, message_factory

_T = descriptor_pb2.FieldDescriptorProto
_SCALAR = {"int64": _T.TYPE_INT64, "uint64": _T.TYPE_UINT64, "double": _T.TYPE_DOUBLE,
           "string": _T.TYPE_STRING, "bytes": _T.TYPE_BYTES}

#: message -> [(field, number, type, repeated)]
_SCHEMA = {
    "XStat": [("metadata_id", 1, "int64", 0), ("double_value", 2, "double", 0),
              ("uint64_value", 3, "uint64", 0), ("int64_value", 4, "int64", 0),
              ("str_value", 5, "string", 0), ("bytes_value", 6, "bytes", 0),
              ("ref_value", 7, "uint64", 0)],
    "XEvent": [("metadata_id", 1, "int64", 0), ("offset_ps", 2, "int64", 0),
               ("duration_ps", 3, "int64", 0), ("stats", 4, "XStat", 1),
               ("num_occurrences", 5, "int64", 0)],
    "XLine": [("id", 1, "int64", 0), ("name", 2, "string", 0), ("timestamp_ns", 3, "int64", 0),
              ("events", 4, "XEvent", 1), ("duration_ps", 9, "int64", 0),
              ("display_id", 10, "int64", 0), ("display_name", 11, "string", 0)],
    "XEventMetadata": [("id", 1, "int64", 0), ("name", 2, "string", 0), ("metadata", 3, "bytes", 0),
                       ("display_name", 4, "string", 0), ("stats", 5, "XStat", 1),
                       ("child_id", 6, "int64", 1)],
    "XStatMetadata": [("id", 1, "int64", 0), ("name", 2, "string", 0), ("description", 3, "string", 0)],
    "EventMetadataEntry": [("key", 1, "int64", 0), ("value", 2, "XEventMetadata", 0)],
    "StatMetadataEntry": [("key", 1, "int64", 0), ("value", 2, "XStatMetadata", 0)],
    "XPlane": [("id", 1, "int64", 0), ("name", 2, "string", 0), ("lines", 3, "XLine", 1),
               ("event_metadata", 4, "EventMetadataEntry", 1),
               ("stat_metadata", 5, "StatMetadataEntry", 1), ("stats", 6, "XStat", 1)],
    "XSpace": [("planes", 1, "XPlane", 1)],
}


def _build():
    fd = descriptor_pb2.FileDescriptorProto(name="bench_xplane.proto", package="benchxplane",
                                            syntax="proto3")
    for msg, fields in _SCHEMA.items():
        m = fd.message_type.add(name=msg)
        for name, number, typ, repeated in fields:
            f = m.field.add(name=name, number=number,
                            label=_T.LABEL_REPEATED if repeated else _T.LABEL_OPTIONAL)
            if typ in _SCALAR:
                f.type = _SCALAR[typ]
            else:
                f.type, f.type_name = _T.TYPE_MESSAGE, f".benchxplane.{typ}"
    pool = descriptor_pool.DescriptorPool()
    pool.Add(fd)
    return message_factory.GetMessageClass(pool.FindMessageTypeByName("benchxplane.XSpace"))


XSpace = _build()


def parse(path: str):
    space = XSpace()
    with open(path, "rb") as f:
        space.ParseFromString(f.read())
    return space
