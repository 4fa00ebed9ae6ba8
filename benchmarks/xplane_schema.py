"""The profiler's file format, read without TensorFlow.

`xplane.desc` is the serialized descriptor of the public
`tsl/profiler/protobuf/xplane.proto` (XSpace, XPlane, XLine, XEvent,
XStat, XEventMetadata, XStatMetadata), copied once from the installed
`tensorflow.tsl.profiler.protobuf.xplane_pb2.DESCRIPTOR.serialized_pb`
(PR 24). JAX's own `ProfileData` reads the same file but leaves out the
per-op metadata (`hlo_category`, `flops`, `bytes_accessed`), which is
where the trace says what kind of op an event was.
"""

from __future__ import annotations

import os

_XSPACE = None


def xspace_class():
    global _XSPACE
    if _XSPACE is None:
        from google.protobuf import (
            descriptor_pb2, descriptor_pool, message_factory,
        )

        here = os.path.dirname(os.path.abspath(__file__))
        with open(os.path.join(here, "xplane.desc"), "rb") as fh:
            proto = descriptor_pb2.FileDescriptorProto.FromString(fh.read())
        proto.name = "benchmarks/xplane.proto"   # a pool of our own
        pool = descriptor_pool.DescriptorPool()
        pool.AddSerializedFile(proto.SerializeToString())
        _XSPACE = message_factory.GetMessageClass(
            pool.FindMessageTypeByName(proto.package + ".XSpace"))
    return _XSPACE


def read_xspace(path: str):
    space = xspace_class()()
    with open(path, "rb") as fh:
        space.ParseFromString(fh.read())
    return space
