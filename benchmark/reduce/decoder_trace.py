"""The decoding layer's own account of a traced generation call.

Since PR 50 `fluid.ProgramDecoder` (`paddle_tpu/fluid/fast_decode.py`)
opens four spans around every public call, `decode/call` >
`decode/prep`, `decode/dispatch`, `decode/fetch`, which reach the
profiler's trace as the executor's and the trainers' do
(program_spans.py); `models/decode.py` puts two scopes in front of the
compiled call's `op_name` paths, `decode_prefill` and `decode_steps`
(`jit(<lambda>)/decode_steps/while/body/closed_call/<type>/~<instance>/
...`); and `obs/telemetry.py` counts calls, programs, tokens, the bytes
of state a call was handed and the seconds of the three phases
(`decoder_*`).  This module reads all three for the five readers under
layer_metrics/ that give the four generation cells one account of a
call: what its shape was (the `decode/call` span's arguments, not a
driver's facts), where the device idled between programs (each gap cut
at the spans' edges and given to the innermost one open), and how long
the device worked inside the prefill, inside the scan of steps, and
there under no op of the Program.

program_spans.py's `PROGRAM_PREFIXES` does not know `decode/`, so the
spans are collected here, as `program_spans.Span`s, and nested with
`program_spans.nest`; the intervals are xplane.py's.  A trace without
the spans or the scopes (a program from before them, or a call that the
compile cache loaded from an entry written before them: `op_name` is not
in the key) gives None from every function here, never 0 and never an
error.
"""

import collections
import functools

from benchmark.reduce import op_instances, op_scopes, program_spans, \
    scans, share_ops, xplane

PREFIX = "decode/"
CALL = "decode/call"
PREFILL_SCOPE, STEPS_SCOPE = "decode_prefill", "decode_steps"

Op = collections.namedtuple("Op", "start end name category path text")
Call = collections.namedtuple("Call", "span args")


# -- the counters ---------------------------------------------------------------

def counters():
    """{`decoder_*` sample: value} of this process's registry, {} for a
    program without them."""
    from paddle_tpu.obs import telemetry

    return {key: value for key, value in telemetry.snapshot().items()
            if key.startswith("decoder_")}


def labelled(found, family, label):
    """{label value: value} of one family's samples with one label."""
    head = "%s{%s=" % (family, label)
    return {key[len(head):-1]: value for key, value in found.items()
            if key.startswith(head)}


# -- the spans ------------------------------------------------------------------

@functools.lru_cache(maxsize=1)
def _host_spans(trace_dir):
    data = program_spans.profile(trace_dir)
    spans, args = [], {}
    for plane in data.planes if data is not None else ():
        if not plane.name.startswith("/host:"):
            continue
        for index, line in enumerate(plane.lines):
            for ev in line.events:
                if not ev.name.startswith((PREFIX, xplane.SPAN_PREFIX)):
                    continue
                span = program_spans.Span(
                    ev.start_ns * 1e-9,
                    (ev.start_ns + ev.duration_ns) * 1e-9, ev.name,
                    (plane.name, index))
                spans.append(span)
                if ev.name == CALL:
                    args[span] = dict(ev.stats)
    return sorted(spans, key=lambda s: (s.start, -s.end)), args


@functools.lru_cache(maxsize=1)
def _traced(trace, trace_dir):
    spans, args = _host_spans(trace_dir)
    spans = [s for s in program_spans.inside(spans, trace.window)
             if s.name != xplane.WINDOW_SPAN]
    return spans, [Call(s, args[s]) for s in spans if s.name == CALL]


def traced(run):
    """(the trace, the `decode/*` and `bench/*` spans inside its window
    but the window's own, the traced `decode/call`s in order) of a traced
    run on a device whose program has the spans, else None."""
    trace = run.reduced
    if run.peaks is None or trace is None or not trace.devices \
            or not run.trace_dir:
        return None
    spans, calls = _traced(trace, run.trace_dir)
    return (trace, spans, calls) if calls else None


def steps_of(call):
    """The steps the call's scan of steps applies: every token but the
    prefill's continuation, or all of them without a prompt."""
    return call.args["max_len"] - (1 if call.args["prompt_len"] else 0)


def prefill_applications(call):
    """The applications of the step a call's prefill makes: a position
    each, or a block of positions each and a shorter one first for the
    remainder."""
    return -(-call.args["prompt_len"] // call.args["block"])


@functools.lru_cache(maxsize=1)
def between_programs(trace, ordinal):
    """The intervals of the traced window in which one device ran no
    operation and no program."""
    device, window = trace.devices[ordinal], trace.window
    gaps = xplane.subtract([window], xplane.busy(device, window))
    running = xplane.clip(xplane.union((s, e) for s, e, _ in device.modules),
                          *window)
    return xplane.subtract(gaps, running)


def idle_by_span(trace, spans, ordinal):
    """Idle seconds of one device between two programs inside the traced
    window, by the innermost span open: a `decode/*` span under its
    name, a span of the benchmark's own as "<name> (no program span)",
    and xplane.NO_SPAN where none was open.  A span's part is the idle
    time inside it less that inside the spans it holds
    (`program_spans.nest`: per host line, by containment), which is what
    cutting each gap at every span's edge comes to.  The parts inside a
    `bench/<name>` span add up to what `xplane.idle_gaps` puts down to
    it."""
    between = between_programs(trace, ordinal)
    inside = [xplane.length(xplane.clip(between, s.start, s.end))
              for s in spans]
    out = collections.Counter({xplane.NO_SPAN: xplane.length(between)})
    for held, (span, parent, _) in zip(inside, program_spans.nest(spans)):
        out[_owner(span)] += held
        out[_owner(parent)] -= held
    return out


def idle_before_program(trace, call, ordinal):
    """Of a call's idle seconds, those before the first program that
    starts inside it: the device waits for the state the call hands
    over (and, under the profiler, for the profiler)."""
    starts = [s for s, _, _ in trace.devices[ordinal].modules
              if call.span.start <= s <= call.span.end]
    return xplane.length(xplane.clip(
        between_programs(trace, ordinal), call.span.start,
        min(starts, default=call.span.end)))


def _owner(span):
    if span is None:
        return xplane.NO_SPAN
    return span.name if span.name.startswith(PREFIX) \
        else program_spans.NO_PROGRAM_SPAN % span.name


# -- the device's operations with their paths -----------------------------------

@functools.lru_cache(maxsize=1)
def _operations(trace_dir, ordinal):
    plane_name = "/device:TPU:%d" % ordinal
    paths = op_scopes.metadata_stat(xplane.find_xplane(trace_dir),
                                    plane_name, "tf_op")
    texts, ops = {}, []
    for plane in program_spans.profile(trace_dir).planes:
        if plane.name != plane_name:
            continue
        for line in plane.lines:
            if line.name != xplane.OPS_LINE:
                continue
            for ev in line.events:
                # one string an instruction, not one an event
                text = ev.name
                text = texts.setdefault(text, text)
                name, category = xplane.parse_instruction(text)
                ops.append(Op(ev.start_ns * 1e-9,
                              (ev.start_ns + ev.duration_ns) * 1e-9, name,
                              category, paths.get(text, ""), text))
    return sorted(ops)


def under(path, scope):
    return scope in op_scopes.components(path)


class Parts:
    """The first device's side of one traced call.  `steps`: the
    outermost `while` operation that holds the operations under
    `decode_steps` (the one with most of their time: an operation the
    compiler moved out of the loop keeps the scope's path and lies
    elsewhere), with that operation's name.  `prefill`: the interval
    from the first to the last operation under `decode_prefill` that
    ends before the scan of steps begins (None without a prompt), and
    `prefill_late`, the seconds of the scope's operations that run later
    (the compiler schedules what only the call's results need, a probe's
    copy, after the steps)."""

    def __init__(self, device, ops, call):
        self.device, self.call = device, call
        lo, hi = call.span.start, call.span.end
        self.ops = [op for op in ops if lo <= op.start and op.end <= hi]
        self.steps = self.steps_name = None
        inside = [op for op in self.ops if under(op.path, STEPS_SCOPE)
                  and op.category not in xplane.CONTAINERS]
        holds = collections.Counter()
        for scan in scans.outermost(device, (lo, hi)):
            holds[scan] = sum(op.end - op.start for op in inside
                              if scan[0] <= op.start and op.end <= scan[1])
        if holds and max(holds.values()) > 0:
            self.steps = max(holds, key=holds.get)
            self.steps_name = next(
                op.name for op in self.ops if op.category == scans.SCAN
                and (op.start, op.end) == self.steps)
        pre = [op for op in self.ops if under(op.path, PREFILL_SCOPE)]
        before = pre if self.steps is None else \
            [op for op in pre if op.end <= self.steps[0]]
        self.prefill = (min(op.start for op in before),
                        max(op.end for op in before)) if before else None
        self.prefill_late = sum(
            op.end - op.start for op in pre
            if op.category not in xplane.CONTAINERS
            and self.prefill is not None and op.start >= self.prefill[1])

    def busy(self, interval):
        return scans.busy_seconds(self.device, interval)

    def work(self, interval):
        """The operations that are work of their own inside `interval`."""
        return [op for op in self.ops
                if op.category not in xplane.CONTAINERS
                and interval[0] <= op.start and op.end <= interval[1]]


@functools.lru_cache(maxsize=1)
def _parts(trace, trace_dir):
    ordinal = min(trace.devices)
    ops = _operations(trace_dir, ordinal)
    return [Parts(trace.devices[ordinal], ops, call)
            for call in _traced(trace, trace_dir)[1]]


def parts(run):
    """[Parts] of the traced calls (made once a run: three readers ask),
    or None (`traced`)."""
    if traced(run) is None:
        return None
    return _parts(run.reduced, run.trace_dir)


def unscoped(part):
    """{category: [seconds, calls, the bytes their instructions state]}
    of the operations inside a call's scan of steps that lie under no op
    instance of the Program: no instance's sigil in the path, or no path
    at all (a `copy`, a `copy-done`, a `slice-done` the compiler added:
    found by time).  None for a program without the sigil."""
    from benchmark.flops import elementwise

    mark = op_instances.sigil()
    if mark is None or part.steps is None:
        return None
    out = collections.defaultdict(lambda: [0.0, 0, 0])
    stated = {}
    for op in part.work(part.steps):
        if share_ops.parts(op.path, mark) is None:
            if op.text not in stated:
                stated[op.text] = elementwise.instruction_bytes(op.text)
            entry = out[op.category]
            entry[0] += op.end - op.start
            entry[1] += 1
            entry[2] += stated[op.text]
    return dict(out)
