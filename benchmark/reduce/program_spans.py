"""The program's own host spans in the profiler's trace.

Every `paddle_tpu.obs.trace.span` is a `jax.profiler.TraceAnnotation`, so
while the benchmark's profiler session runs the program's spans
(`executor/run`, `executor/segment`, `parallel/step`, `serving/batch`, ...)
lie on the host line of the same `.xplane.pb` as the benchmark's own
`bench/*` spans, on the clock of the device's operations.  This reads them
(the host plane of the file `xplane.load` reduced, once more through
`jax.profiler.ProfileData`) and does three things with them:

- nests them by containment, per line, and gives each its self time: its
  duration minus its children's;
- averages the duration of the spans of one name inside the window;
- shares the device's idle time between two programs out among them: a
  gap is cut wherever a span starts or ends, and each piece goes to the
  innermost program span open at that time (of the spans open, the one
  that started last), to "bench/<name> (no program span)" where only a
  span of the benchmark's own is open, and to xplane.NO_SPAN where none
  is.  The pieces inside the `bench/<name>` spans add up to what
  `xplane.idle_gaps` puts down to `bench/<name>`.

A trace without such spans (a program from before they existed) gives
empty results, never an error.
"""

import collections
import functools

from benchmark.reduce import xplane

PROGRAM_PREFIXES = ("executor/", "parallel/", "serving/")
NO_PROGRAM_SPAN = "%s (no program span)"

Span = collections.namedtuple("Span", "start end name line")


@functools.lru_cache(maxsize=1)
def profile(trace_dir):
    """The newest trace under `trace_dir` as `ProfileData` (kept, so that
    every reader of one run parses the file once), or None."""
    from jax.profiler import ProfileData

    path = xplane.find_xplane(trace_dir) if trace_dir else None
    return None if path is None else ProfileData.from_file(path)


def from_profile(data):
    """The program's and the benchmark's spans of every host line, sorted
    by start (an enclosing span before what it encloses)."""
    spans = []
    for plane in data.planes:
        if not plane.name.startswith("/host:"):
            continue
        for index, line in enumerate(plane.lines):
            for ev in line.events:
                if ev.name.startswith(PROGRAM_PREFIXES + (xplane.SPAN_PREFIX,)):
                    spans.append(Span(
                        ev.start_ns * 1e-9,
                        (ev.start_ns + ev.duration_ns) * 1e-9, ev.name,
                        (plane.name, index)))
    return sorted(spans, key=lambda s: (s.start, -s.end))


def is_program(span):
    return span.name.startswith(PROGRAM_PREFIXES)


def inside(spans, window):
    return [s for s in spans if window[0] <= s.start and s.end <= window[1]]


def nest(spans):
    """[(span, parent or None, self seconds)]: per line, a span's parent
    is the innermost span that contains it."""
    children = collections.defaultdict(float)
    parents = {}
    open_by_line = collections.defaultdict(list)
    for i, span in enumerate(spans):
        stack = open_by_line[span.line]
        while stack and spans[stack[-1]].end < span.end:
            stack.pop()
        if stack:
            parents[i] = stack[-1]
            children[stack[-1]] += span.end - span.start
        stack.append(i)
    return [(span, spans[parents[i]] if i in parents else None,
             span.end - span.start - children[i])
            for i, span in enumerate(spans)]


def self_seconds(spans):
    """{name: self seconds} summed over the spans of each name."""
    out = collections.Counter()
    for span, _, own in nest(spans):
        out[span.name] += own
    return out


def mean_seconds(spans, name):
    """(mean duration, count) of the spans called `name`."""
    found = [s.end - s.start for s in spans if s.name == name]
    return (sum(found) / len(found), len(found)) if found else (0.0, 0)


def owner(open_spans):
    """Whom an idle piece goes to, given the spans open during it."""
    program = [s for s in open_spans if is_program(s)]
    if program:
        return max(program, key=lambda s: s.start).name
    if open_spans:
        return NO_PROGRAM_SPAN % min(open_spans, key=lambda s: s.start).name
    return xplane.NO_SPAN


def idle_by_span(trace, spans, ordinal, window=None):
    """Idle seconds of one device between two programs, inside the
    window, by the span they go to (see the module's docstring)."""
    device = trace.devices[ordinal]
    window = window or trace.window
    gaps = xplane.subtract([window], xplane.busy(device, window))
    running = xplane.clip(xplane.union((s, e) for s, e, _ in device.modules),
                          *window)
    spans = [s for s in spans if s.name != xplane.WINDOW_SPAN]
    out = collections.Counter()
    first = 0
    for lo, hi in xplane.subtract(gaps, running):
        # the gaps are in order: a span that ended before this one
        # reaches no later one either
        while first < len(spans) and spans[first].end <= lo:
            first += 1
        near = []
        for span in spans[first:]:
            if span.start >= hi:
                break
            if span.end > lo:
                near.append(span)
        cuts = sorted({lo, hi} | {t for s in near for t in (s.start, s.end)
                                  if lo < t < hi})
        for a, b in zip(cuts, cuts[1:]):
            middle = (a + b) / 2
            out[owner([s for s in near if s.start <= middle < s.end])] \
                += b - a
    return out


# -- what the per-layer readers share -----------------------------------------

def device_trace(run):
    """(trace, profile) of a traced run on a device, else None: a CPU
    time is never printed under the name of a device's layer."""
    trace = run.reduced
    if run.peaks is None or trace is None or not trace.devices \
            or not run.facts.get("traced_steps"):
        return None
    data = profile(run.trace_dir)
    return None if data is None else (trace, data)


def traced(run):
    """(trace, the spans inside its traced window) of such a run."""
    found = device_trace(run)
    if found is None:
        return None
    trace, data = found
    return trace, inside(from_profile(data), trace.window)


def host_ms(run, name):
    """Mean milliseconds of the spans called `name` in the traced window,
    or None where there is none; prints the self times under them, a
    step's worth each."""
    found = traced(run)
    if found is None:
        return None
    _, spans = found
    mean, count = mean_seconds(spans, name)
    if not count:
        return None
    prefix = name.split("/")[0] + "/"
    own = self_seconds([s for s in spans if s.name.startswith(prefix)])
    print("%s: %d spans, mean %.3f ms; self time a span: %s"
          % (name, count, mean * 1e3, ", ".join(
              "%s %.3f ms" % (n, s / count * 1e3)
              for n, s in own.most_common())), flush=True)
    return mean * 1e3


def idle_ms_per_step(run, prefix):
    """Milliseconds a traced step for which the first device idled
    between two programs while a span of the layer `prefix` was the
    innermost open, or None where the trace has no such span; prints the
    split by span, and what the benchmark's own spans keep."""
    found = traced(run)
    if found is None:
        return None
    trace, spans = found
    if not any(s.name.startswith(prefix) for s in spans):
        return None
    steps = run.facts["traced_steps"]
    idle = idle_by_span(trace, spans, min(trace.devices))
    print("device idle between programs, ms a step, by the innermost "
          "span open: %s" % ", ".join(
              "%s %.3f" % (n, s / steps * 1e3)
              for n, s in idle.most_common()), flush=True)
    return sum(s for n, s in idle.items()
               if n.startswith(prefix)) / steps * 1e3
