"""``{instruction: op name}`` for the step program's device ops, read
from the traced run's own ``.xplane.pb``: what every cell already has,
whatever its driver hands over.

Where the op name lives in a TPU v5e trace (read on the chip, PR 34,
jax 0.9.0 / libtpu 0.0.34): NOT on the ``XLA Ops`` event, whose three
stats are ``device_offset_ps``, ``device_duration_ps`` and ``Time Scale
Multiplier`` and whose name is the instruction's text without
``metadata={...}``, which is all ``jax.profiler.ProfileData`` shows.
It is one level down, on the event's METADATA (``XPlane.event_metadata``,
one entry an instruction, named by the instruction's whole text): a
string stat ``tf_op`` holding ``<op name>:`` (the profiler's
``name:type`` with the type left empty), beside ``hlo_category``,
``flops``, ``bytes_accessed`` and ``program_id``.  A fusion carries the
op name the compiler gave it, as in the compiled program's text (the
convolution's or the product's where it holds one, whatever rides
along; its root's otherwise); an instruction
the compiler added of its own (``copy-start``) has no ``tf_op``.
``ProfileData`` has no door to the metadata's stats, so the file's
protobuf wire format is walked here, by field numbers of
``tsl/profiler/protobuf/xplane.proto``, and only as deep as the
metadata: the lines and their events are stepped over whole.

The file is read a second time here, after the window has closed
(``lib/trace.py`` keeps names and times only); a line of the run says
what that cost.
"""

import time

from benchmarks.lib import trace
from benchmarks.lib.harness import log

KEY = "_op_names"
_STAT = "tf_op"
# field numbers: XSpace.planes; XPlane.name, .event_metadata,
# .stat_metadata; a map entry's value; XEventMetadata.name, .stats;
# XStatMetadata.id, .name; XStat.metadata_id, .str_value
_PLANES, _PLANE_NAME, _EVENT_METADATA, _STAT_METADATA = 1, 2, 4, 5
_MAP_VALUE, _META_NAME, _META_STATS = 2, 2, 5
_STAT_ID, _STAT_NAME, _STAT_REF, _STAT_STR = 1, 2, 1, 5


def _varint(buf, at):
    value = shift = 0
    while True:
        byte = buf[at]
        at += 1
        value |= (byte & 0x7F) << shift
        if byte < 0x80:
            return value, at
        shift += 7


def _fields(buf):
    """``(field number, value)`` of one message: an int for a varint, a
    memoryview for everything else."""
    at, end = 0, len(buf)
    while at < end:
        key, at = _varint(buf, at)
        wire = key & 7
        if wire == 0:
            value, at = _varint(buf, at)
        else:
            if wire == 2:
                size, at = _varint(buf, at)
            elif wire in (1, 5):
                size = 8 if wire == 1 else 4
            else:
                raise ValueError(f"wire type {wire} at byte {at}")
            value, at = buf[at:at + size], at + size
        yield key >> 3, value


def _text(view):
    return bytes(view).decode("utf-8", "replace")


def _entry(view):
    """A map entry's value."""
    return next(v for k, v in _fields(view) if k == _MAP_VALUE)


def _plane_op_names(plane):
    stat_ids, metadata = set(), []
    for number, value in plane:
        if number == _STAT_METADATA:
            stat = dict(_fields(_entry(value)))
            if _text(stat.get(_STAT_NAME, b"")) == _STAT:
                stat_ids.add(stat.get(_STAT_ID, 0))
        elif number == _EVENT_METADATA:
            metadata.append(value)
    names = {}
    for view in metadata:
        name, op_name = None, None
        for number, value in _fields(_entry(view)):
            if number == _META_NAME:
                name = trace.instruction(_text(value))
            elif number == _META_STATS:
                stat = dict(_fields(value))
                if stat.get(_STAT_REF) in stat_ids and _STAT_STR in stat:
                    op_name = _text(stat[_STAT_STR])
        if name and op_name:
            names.setdefault(name, op_name.rpartition(":")[0] or op_name)
    return names


def from_xplane(path):
    """``{instruction: op name}`` for every instruction of the first
    device's plane whose metadata carries one."""
    with open(path, "rb") as f:
        space = memoryview(f.read())
    planes = {}
    for number, value in _fields(space):
        if number != _PLANES:
            continue
        plane = list(_fields(value))
        name = next((_text(v) for k, v in plane if k == _PLANE_NAME), "")
        if name.startswith(trace.DEVICE_PLANE):
            planes[name] = plane
    return _plane_op_names(planes[min(planes)]) if planes else {}


def read(ctx):
    """The map for this run's trace, memoised on ``ctx``; None where the
    run has no trace (a rehearsal) or its file is gone."""
    if KEY not in ctx:
        ctx[KEY] = None
        if ctx["trace"] is not None:
            t0 = time.perf_counter()
            try:
                ctx[KEY] = from_xplane(
                    trace.xplane_path(ctx["window"].run.trace_dir))
            except FileNotFoundError:
                pass
            else:
                ops = ctx["trace"]["op_self_s"]
                log("op_names", instructions=len(ops),
                    named=sum(ins in ctx[KEY] for ins in ops),
                    seconds=f"{time.perf_counter() - t0:.2f}")
    return ctx[KEY]
