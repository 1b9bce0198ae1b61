"""Device time by the program's own op names.

`fluid.executor.apply_op` runs every op under `jax.named_scope(<op type>)`
and the flash backward under `flash_attention_bwd`, so each instruction of
the compiled program carries an `op_name` path such as
`jit(segment_fn)/conv2d_grad/transpose(jvp(conv2d))/conv_general_dilated`.
XLA:TPU hands that path to the profiler as the `tf_op` stat of the
operation's *event metadata* (libtpu 0.0.34; a fusion has the path of its
root).  `jax.profiler.ProfileData` gives an event's own stats only, so the
metadata is read here from the `.xplane.pb` itself: a protobuf is a
sequence of (field number, wire type, value), and the few fields needed
(tsl/profiler/protobuf/xplane.proto: a plane's name and its map of event
metadata, a metadata's name and stats, a stat's string or reference) are
found by walking that sequence; the lines and their events, nearly all of
the file, are skipped whole.

An operation's path is joined to the device events by the text of its
HLO instruction, which is both the event's name and the metadata's.  The
op type is the path's first component that is not a `jit(...)` wrapper,
provided something follows it (the last component is the JAX primitive,
and `mul` or `transpose` is the name of a primitive as well as of an
op).  The pass follows from the type: `<forward>_grad` is backward, the
`op_type` of a `fluid.optimizer.Optimizer` subclass is optimizer, the
rest forward.  An operation with no path, or one outside every op's
scope, is `unscoped`.
"""

import collections
import functools
import re

from benchmark.reduce import program_spans, xplane

UNSCOPED = "unscoped"
PASSES = ("forward", "backward", "optimizer", UNSCOPED)
JIT_WRAPPER = re.compile(r"^\w*jit\(.*\)$")
# "transpose(jvp(flash_attention_bwd))" -> "flash_attention_bwd"
TRANSFORMED = re.compile(r"^\w+\((.*)\)$")

# -- the protobuf wire format -------------------------------------------------

VARINT, FIXED64, BYTES, FIXED32 = 0, 1, 2, 5


def _varint(buf, pos):
    value = shift = 0
    while True:
        byte = buf[pos]
        pos += 1
        value |= (byte & 0x7F) << shift
        if byte < 0x80:
            return value, pos
        shift += 7


def fields(buf):
    """(field number, wire type, value) of one message; a length-delimited
    value is a memoryview, a varint an int, fixed-width ones are skipped."""
    pos, end = 0, len(buf)
    while pos < end:
        key, pos = _varint(buf, pos)
        number, wire = key >> 3, key & 7
        if wire == VARINT:
            value, pos = _varint(buf, pos)
        elif wire == BYTES:
            size, pos = _varint(buf, pos)
            value = buf[pos:pos + size]
            pos += size
        elif wire == FIXED64:
            value, pos = None, pos + 8
        elif wire == FIXED32:
            value, pos = None, pos + 4
        else:
            raise ValueError("wire type %d in an xplane file" % wire)
        yield number, wire, value


def _text(view):
    return bytes(view).decode("utf-8", "replace")


def metadata_stat(path, plane_name, stat_name):
    """{event metadata name: the string value of its stat `stat_name`} of
    the plane called `plane_name`; {} where the plane or the stat is not
    there.  XSpace.planes = 1; XPlane.name = 2, .event_metadata = 4,
    .stat_metadata = 5 (map entries: key = 1, value = 2);
    XEventMetadata.name = 2, .stats = 5; XStatMetadata.id = 1, .name = 2;
    XStat.metadata_id = 1, .str_value = 5, .ref_value = 7 (the id of a
    stat metadata whose name is the value)."""
    with open(path, "rb") as f:
        space = memoryview(f.read())
    for number, wire, plane in fields(space):
        if number != 1 or wire != BYTES:
            continue
        name, events, stats = None, [], {}
        for number, wire, value in fields(plane):
            if number == 2 and wire == BYTES:
                name = _text(value)
                if name != plane_name:
                    break
            elif number == 4 and wire == BYTES:
                events.append(value)
            elif number == 5 and wire == BYTES:
                for n, w, v in fields(value):
                    if n == 2 and w == BYTES:
                        meta = {n2: v2 for n2, _, v2 in fields(v)}
                        stats[meta.get(1, 0)] = _text(meta.get(2, b""))
        if name != plane_name:
            continue
        wanted = {i for i, n in stats.items() if n == stat_name}
        out = {}
        for entry in events:
            for n, w, v in fields(entry):
                if n != 2 or w != BYTES:
                    continue
                event_name, found = None, None
                for n2, w2, v2 in fields(v):
                    if n2 == 2 and w2 == BYTES:
                        event_name = _text(v2)
                    elif n2 == 5 and w2 == BYTES:
                        stat = {n3: v3 for n3, _, v3 in fields(v2)}
                        if stat.get(1) in wanted:
                            found = (_text(stat[5]) if 5 in stat
                                     else stats.get(stat.get(7), ""))
                if event_name is not None and found is not None:
                    out[event_name] = found
        return out
    return {}


# -- from a path to an op type and a pass -------------------------------------

@functools.lru_cache(maxsize=None)
def components(path):
    """The scopes of an `op_name` path, outermost first, each without the
    transformations JAX wrapped it in; the profiler's `tf_op` ends in a
    colon, and XLA joins the paths of merged instructions with ';' (the
    first is taken).  (Kept: a trace has a few hundred paths and a few
    hundred thousand operations.)"""
    path = path.split(";")[0].rstrip(":")
    out = []
    for part in path.split("/"):
        while True:
            inner = TRANSFORMED.match(part)
            if not inner or JIT_WRAPPER.match(part):
                break
            part = inner.group(1)
        out.append(part)
    return tuple(out)


@functools.lru_cache(maxsize=None)
def op_type(path):
    """The program's op type a path lies under, or None."""
    parts = [p for p in components(path) if not JIT_WRAPPER.match(p)]
    return parts[0] if len(parts) > 1 and parts[0] else None


def optimizer_op_types():
    """The `op_type` of every `fluid.optimizer.Optimizer` subclass."""
    from paddle_tpu.fluid import optimizer

    found, todo = set(), [optimizer.Optimizer]
    while todo:
        cls = todo.pop()
        todo.extend(cls.__subclasses__())
        if getattr(cls, "op_type", None):
            found.add(cls.op_type)
    return found


def pass_of(kind, optimizers):
    if kind is None:
        return UNSCOPED
    if kind.endswith("_grad"):
        return "backward"
    return "optimizer" if kind in optimizers else "forward"


# -- device time ---------------------------------------------------------------

class Scoped:
    """The operations of one device with the path each lies under."""

    def __init__(self, ops, window):
        self.ops = ops          # [(start, end, instruction name, path)]
        self.window = window

    def seconds(self, key, name_fragment=""):
        """{key(path): [seconds, calls]} inside the window, of the
        operations whose instruction name holds `name_fragment`."""
        out = collections.defaultdict(lambda: [0.0, 0])
        for start, end, name, path in self.ops:
            if name_fragment not in name:
                continue
            for s, e in xplane.clip([(start, end)], *self.window):
                entry = out[key(path)]
                entry[0] += e - s
                entry[1] += 1
        return out

    def under(self, scope):
        """(seconds, calls) of the operations under a scope called
        `scope`, at any depth."""
        found = self.seconds(lambda path: scope in components(path))
        return tuple(found[True])

    def names_its_ops(self):
        """Whether the program opened a scope per op at all: of the
        device time of the instructions that came from traced code (a
        path that starts with `jit(...)`; the copies XLA adds have none),
        at least nine tenths lie under an op type (seen on the chip: all
        of it).  A program from before the scopes has a few paths that
        look like one (`while`, `cond` are JAX's scopes as well as op
        types: a fifth of GPT-2's time) and fails this."""
        traced = self.seconds(
            lambda path: None if not JIT_WRAPPER.match(components(path)[0])
            else op_type(path) is not None)
        return traced[True][0] > 9 * traced[False][0]


def scoped(profile, paths, ordinal, window):
    """The work of device `ordinal` (containers left out, as everywhere
    in xplane.py) with its paths: `profile` is the `ProfileData`, `paths`
    the `metadata_stat(..., "tf_op")` of that device's plane."""
    plane_name = "/device:TPU:%d" % ordinal
    ops = []
    for plane in profile.planes:
        if plane.name != plane_name:
            continue
        for line in plane.lines:
            if line.name != xplane.OPS_LINE:
                continue
            for ev in line.events:
                name, category = xplane.parse_instruction(ev.name)
                if category in xplane.CONTAINERS:
                    continue
                ops.append((ev.start_ns * 1e-9,
                            (ev.start_ns + ev.duration_ns) * 1e-9, name,
                            paths.get(ev.name, "")))
    return Scoped(ops, window)


# -- what the per-layer readers share -----------------------------------------

@functools.lru_cache(maxsize=1)
def _of_trace(trace_dir, ordinal, window):
    paths = metadata_stat(xplane.find_xplane(trace_dir),
                          "/device:TPU:%d" % ordinal, "tf_op")
    found = scoped(program_spans.profile(trace_dir), paths, ordinal, window)
    return found if found.names_its_ops() else None


def of_run(run):
    """The first device's operations of a traced run with their paths,
    or None: no device, no trace, or a program that names no op."""
    found = program_spans.device_trace(run)
    if found is None:
        return None
    trace, _ = found
    return _of_trace(run.trace_dir, min(trace.devices), trace.window)


def pass_ms_per_step(run, which, report=False):
    """Device milliseconds a traced step spends in one pass, or None;
    with `report`, prints the passes and the ten op types with most
    time."""
    found = of_run(run)
    if found is None:
        return None
    steps = run.facts["traced_steps"]
    optimizers = optimizer_op_types()
    by_pass = found.seconds(lambda path: pass_of(op_type(path), optimizers))
    if report:
        print("device ms a step by pass: %s" % ", ".join(
            "%s %.3f" % (p, by_pass[p][0] / steps * 1e3) for p in PASSES),
            flush=True)
        by_type = found.seconds(op_type)
        by_type.pop(None, None)
        print("device ms a step by op type: %s" % ", ".join(
            "%s %.3f (x%.0f)" % (kind, s / steps * 1e3, calls / steps)
            for kind, (s, calls) in sorted(
                by_type.items(), key=lambda item: -item[1][0])[:10]),
            flush=True)
    if not by_pass[which][1]:
        return None
    return by_pass[which][0] / steps * 1e3
