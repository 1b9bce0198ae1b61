"""From the profiler's trace to numbers: the one reduction every PR's
per-layer device metrics go through.

`load(trace_dir)` reads the `.xplane.pb` the JAX profiler wrote, with
nothing but `jax.profiler.ProfileData`, into a `Trace`:

- per device (`/device:TPU:<n>` planes), the operations of its "XLA Ops"
  line as (start, end, name, category) in seconds on the trace's clock,
  the asynchronous operations of its "Async XLA Ops" line (each from its
  `-start` to its `-done`), and the program executions of its
  "XLA Modules" line;
- the benchmark's own host spans (`jax.profiler.TraceAnnotation`s whose
  name starts with "bench/"), on the same clock.

An operation's event carries the text of its HLO instruction and no
category (libtpu 0.0.34 through `ProfileData`), so the category is read
from the text: the opcode, and for a fusion its kind.  XLA:TPU emits a
convolution or matrix product together with what it fused around it as a
fusion of kind `kOutput`; elementwise and reduction loops are `kLoop` and
`kInput`.  "MXU" time is therefore the `kOutput` fusions plus any unfused
`convolution` or `dot`.

Everything else here is arithmetic on intervals: the union of busy
intervals, time per category, collectives and their exposed part, idle
gaps attributed to the host span they fall in.  Control-flow operations
(`while`, `conditional`, `call`) only contain other operations and are
left out of every sum.
"""

import collections
import glob
import os
import re

Op = collections.namedtuple("Op", "start end name category")

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
ASYNC_LINE = "Async XLA Ops"
MODULES_LINE = "XLA Modules"
INSTRUCTION = re.compile(
    r"^%(?P<name>\S+) = .*? (?P<opcode>[a-z][a-z0-9-]*)\(")
FUSION_KIND = re.compile(r"kind=k(\w+)")
SPAN_PREFIX = "bench/"
WINDOW_SPAN = "bench/window"
CONTAINERS = ("while", "conditional", "call")
COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter",
               "collective-permute", "all-to-all")
MXU_CATEGORIES = ("output fusion", "convolution", "dot")
NO_SPAN = "(no benchmark span)"
IN_PROGRAM = "(inside a running program)"


class Device:
    def __init__(self, ops, modules, async_ops=()):
        self.ops = sorted(ops)
        self.async_ops = sorted(async_ops)
        self.modules = sorted(modules)
        # the operations that are work of their own, containers left out
        self.work = [op for op in self.ops if op.category not in CONTAINERS]


class Trace:
    def __init__(self, devices, spans):
        self.devices = devices          # ordinal -> Device
        self.spans = sorted(spans)      # (start, end, name)

    @property
    def window(self):
        """(start, end) of the traced window: the benchmark's own
        "bench/window" span, else the extent of everything recorded."""
        for start, end, name in self.spans:
            if name == WINDOW_SPAN:
                return start, end
        starts = [d.ops[0].start for d in self.devices.values() if d.ops]
        ends = [max(op.end for op in d.ops)
                for d in self.devices.values() if d.ops]
        return (min(starts), max(ends)) if starts else (0.0, 0.0)


def find_xplane(trace_dir):
    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    return paths[-1] if paths else None


def parse_instruction(text):
    """(name, category) of the HLO instruction an event's name spells out:
    "%fusion.7 = bf16[8]{0} fusion(...), kind=kOutput, calls=..." gives
    ("fusion.7", "output fusion").  A name that is not an instruction
    (another runtime's plain operation name) is its own opcode, without
    the numeric suffix."""
    match = INSTRUCTION.match(text)
    if not match:
        name = text.lstrip("%")
        return name, re.sub(r"[.\d]+$", "", name)
    name, opcode = match.group("name"), match.group("opcode")
    if opcode == "fusion":
        kind = FUSION_KIND.search(text)
        return name, (kind.group(1).lower() if kind else "") + " fusion"
    return name, opcode


def _ops(line):
    out = []
    for ev in line.events:
        name, category = parse_instruction(ev.name)
        out.append(Op(ev.start_ns * 1e-9,
                      (ev.start_ns + ev.duration_ns) * 1e-9, name, category))
    return out


def load(trace_dir):
    """The newest trace under `trace_dir` as a `Trace`, or None when
    there is none."""
    from jax.profiler import ProfileData

    path = find_xplane(trace_dir)
    if path is None:
        return None
    return from_profile(ProfileData.from_file(path))


def from_profile(profile):
    devices, spans = {}, []
    for plane in profile.planes:
        match = DEVICE_PLANE.match(plane.name)
        if match:
            ops, modules, async_ops = [], [], []
            for line in plane.lines:
                if line.name == OPS_LINE:
                    ops = _ops(line)
                elif line.name == ASYNC_LINE:
                    async_ops = _ops(line)
                elif line.name == MODULES_LINE:
                    for ev in line.events:
                        modules.append((
                            ev.start_ns * 1e-9,
                            (ev.start_ns + ev.duration_ns) * 1e-9, ev.name))
            devices[int(match.group(1))] = Device(ops, modules, async_ops)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(SPAN_PREFIX):
                        spans.append((
                            ev.start_ns * 1e-9,
                            (ev.start_ns + ev.duration_ns) * 1e-9, ev.name))
    return Trace(devices, spans)


# -- interval arithmetic ------------------------------------------------------

def union(intervals):
    """Disjoint, sorted intervals covering the same points."""
    merged = []
    for start, end in sorted(intervals):
        if merged and start <= merged[-1][1]:
            if end > merged[-1][1]:
                merged[-1][1] = end
        else:
            merged.append([start, end])
    return [(s, e) for s, e in merged]


def clip(intervals, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if min(e, hi) > max(s, lo)]


def length(intervals):
    return sum(e - s for s, e in intervals)


def subtract(intervals, holes):
    """The part of disjoint sorted `intervals` not covered by disjoint
    sorted `holes`."""
    out = []
    holes = list(holes)
    j = 0
    for start, end in intervals:
        cur = start
        while j < len(holes) and holes[j][1] <= cur:
            j += 1
        k = j
        while k < len(holes) and holes[k][0] < end:
            if holes[k][0] > cur:
                out.append((cur, holes[k][0]))
            cur = max(cur, holes[k][1])
            k += 1
        if cur < end:
            out.append((cur, end))
    return out


def busy(device, window):
    """Disjoint intervals inside `window` in which an operation ran."""
    return clip(union((op.start, op.end) for op in device.work), *window)


def busy_seconds(trace):
    """Seconds an operation ran, averaged over the traced devices."""
    if not trace.devices:
        return 0.0
    window = trace.window
    return sum(length(busy(d, window))
               for d in trace.devices.values()) / len(trace.devices)


def category_seconds(device, window):
    """Seconds by category, each operation clipped to the window."""
    out = collections.Counter()
    for op in device.work:
        for s, e in clip([(op.start, op.end)], *window):
            out[op.category] += e - s
    return out


def op_seconds(device, window):
    """{(name, category): [seconds, calls]} with the instruction's numeric
    suffix dropped, so that the calls of one kernel, and the fusions XLA
    named alike, add up."""
    out = collections.defaultdict(lambda: [0.0, 0])
    for op in device.work:
        for s, e in clip([(op.start, op.end)], *window):
            entry = out[re.sub(r"\.\d+$", "", op.name), op.category]
            entry[0] += e - s
            entry[1] += 1
    return out


def seconds_named(device, window, fragment):
    """(seconds, calls) of the operations whose name holds `fragment`."""
    spans = [c for op in device.work if fragment in op.name
             for c in clip([(op.start, op.end)], *window)]
    return length(spans), len(spans)


def is_collective(op):
    return op.category.startswith(COLLECTIVES)


def collective_seconds(device, window):
    """(total, exposed): seconds in which a collective was under way on
    the device, and the part of them in which no other operation ran on
    it.  A synchronous collective is one operation of the "XLA Ops" line;
    an asynchronous one runs from its `-start` to its `-done`, which is
    one event of the "Async XLA Ops" line, and whatever else the core
    does in between hides that much of it."""
    ops = device.work
    coll = clip(union((op.start, op.end)
                      for op in ops + device.async_ops
                      if is_collective(op)), *window)
    other = clip(union((op.start, op.end) for op in ops
                       if not is_collective(op)), *window)
    return length(coll), length(subtract(coll, other))


def idle_gaps(trace, ordinal, window=None):
    """Idle seconds of one device inside the window, by what the host
    was doing.  The part of a gap that lies inside a running program is
    the program's own; the rest is shared out among the benchmark's
    spans by how much of it each covers, earlier spans first, and what
    no span covers is left without one."""
    device = trace.devices[ordinal]
    window = window or trace.window
    gaps = subtract([window], busy(device, window))
    running = clip(union((s, e) for s, e, _ in device.modules), *window)
    between = subtract(gaps, running)
    out = collections.Counter()
    if length(gaps) > length(between):
        out[IN_PROGRAM] = length(gaps) - length(between)
    for start, end, name in trace.spans:
        if name == WINDOW_SPAN:
            continue
        covered = clip(between, start, end)
        if covered:
            out[name] += length(covered)
            between = subtract(between, covered)
    if between:
        out[NO_SPAN] = length(between)
    return out
