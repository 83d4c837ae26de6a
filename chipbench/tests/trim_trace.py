"""Trim a traced run's ``.xplane.pb`` into a test fixture of under 1 MB.

    PYTHONPATH=. python chipbench/tests/trim_trace.py <in.xplane.pb> <out.xplane.pb>

The input is a profiler trace of a few steps on the chip, made as
``harness.traced`` makes it (``jax.profiler.start_trace`` into a directory
of your own, the steps under the ``bench.trace`` span, ``stop_trace``);
``harness.traced`` itself deletes its trace once reduced.

Keeps what ``chipbench.tracing`` reads: the device planes' operation lines
and the host's ``bench.*`` spans.  Each operation's name is cut to its HLO
name (``%fusion.12 = ...`` -> ``fusion.12``) and event stats are dropped.
Needs TensorFlow's copy of the XPlane protobuf, used by hand only.
"""

import sys

from tensorflow.tsl.profiler.protobuf import xplane_pb2

from chipbench import tracing


def trim(src: str, dst: str) -> None:
    space = xplane_pb2.XSpace()
    with open(src, "rb") as f:
        space.ParseFromString(f.read())
    keep = []
    for plane in space.planes:
        device = tracing._DEVICE.match(plane.name)
        if not device and not plane.name.startswith("/host"):
            continue
        names = {k: m.name for k, m in plane.event_metadata.items()}
        lines = []
        for line in plane.lines:
            if device and line.name not in (tracing.OPS_LINE,
                                            tracing.ASYNC_LINE):
                continue
            events = [e for e in line.events
                      if device or names.get(e.metadata_id, "").startswith(
                          "bench.")]
            if not events:
                continue
            del line.events[:]
            for e in events:
                del e.stats[:]
                line.events.append(e)
            lines.append(line)
        del plane.lines[:]
        plane.lines.extend(lines)
        used = {e.metadata_id for line in plane.lines for e in line.events}
        for k in list(plane.event_metadata):
            if k not in used:
                del plane.event_metadata[k]
            else:
                m = plane.event_metadata[k]
                name = tracing.op_name(m.name) if device else m.name
                m.Clear()
                m.id, m.name = k, name
        plane.stat_metadata.clear()
        del plane.stats[:]
        keep.append(plane)
    del space.planes[:]
    space.planes.extend(keep)
    with open(dst, "wb") as f:
        f.write(space.SerializeToString())


if __name__ == "__main__":
    trim(sys.argv[1], sys.argv[2])
