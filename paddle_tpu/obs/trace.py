"""Span tracer: nested, thread-safe, with two sinks.

Every `span(...)` is a `jax.profiler.TraceAnnotation`: while a profiler
session runs (`jax.profiler.start_trace`,
`fluid.profiler.profiler(trace_dir=...)`) it lands on the host line of
the profiler's own trace, on the clock the device's operations are on,
nested by containment beside JAX's `PjitFunction(...)` and
`PjRt...Execute` events, its arguments as the event's stats.  The
profiler's own check decides whether it records; with no session it is
one object made and dropped.  While `obs.trace` is enabled
(`enable()`/`tracing()`) the same span is also kept in memory and is
exportable as Chrome trace-event JSON (the `{"traceEvents": [...]}`
format Perfetto and chrome://tracing load directly); `obs.tail`
and `obs_dump` read that sink.

Spans are recorded as complete ("X") events — begin timestamp plus
duration — which Perfetto nests by containment per thread track, so
plain `with span(...)` nesting in python shows up as a flame graph
without begin/end pairing bookkeeping.  Instant ("i") events mark
moments rather than ranges (jit trace/compile detections).

Concurrency model: one global event list behind a lock, appended to
only at span *exit* (one append per span), with per-thread track ids
and thread-name metadata emitted lazily.  The disabled path is one
module-level flag check and the bare annotation (under a microsecond a
span), so leaving tracing off costs nothing measurable on the executor
hot path.  `emit_span` and `instant` record after the fact or at a
point, which the profiler's annotation cannot, so they reach the
in-memory sink only.

The buffer is bounded (`max_events`); once full, new events are
dropped and counted (`dropped_events()`), never silently swallowed:
the export embeds the drop count as process metadata.

The start-up timeline is a second, small list that is always kept: a
span of `cat="startup"` (and an `emit_span` of it) leaves one event
there whether or not `enable()` was called, and `enable(clear=True)`
and `reset()` leave the list alone.  Its events are on
`time.perf_counter()` in seconds, know the thread they ran on and the
start-up event that was open on it when they began (`parent`), and
come from the places where the program does its once-a-process work
(the package's import, a Program's backward and optimizer passes, an
executor's plan and first run, a trainer's `init` and first step, a
decoder's construction and each program it builds, JAX's trace, lower
and compile phases, a load from disk): nothing on a steady-state step
appends to it.  `startup_summary()` splits the time to the first
answer by it; `obs_dump --startup` prints that as a table.
"""

import json
import threading
import time

from jax.profiler import TraceAnnotation

__all__ = ["enable", "disable", "is_enabled", "reset", "tracing",
           "span", "instant", "emit_span", "events", "event_count",
           "events_since", "epoch", "dropped_events",
           "startup_events", "startup_summary",
           "export_chrome_trace", "to_chrome_trace"]

_lock = threading.Lock()
_enabled = False
_events = []            # raw event dicts (chrome trace-event shape)
_dropped = 0
_max_events = 1_000_000
_epoch = time.perf_counter()   # ts are µs relative to this
_tls = threading.local()
_thread_meta_done = set()      # tids that already emitted thread_name
_PID = 1                       # single-process trace; constant pid

STARTUP = "startup"            # the cat of the start-up timeline
_JIT_PHASE_PREFIX = "startup/jit_"
_startup = []                  # its events, in the order they began
_startup_children = []         # how many events name each as `parent`
_startup_dropped = 0
_STARTUP_MAX = 4096
_STARTUP_KEPT_FREE = 256       # of them, for events that are no jit phase


def _now_us():
    return (time.perf_counter() - _epoch) * 1e6


def _tid():
    tid = getattr(_tls, "tid", None)
    if tid is None:
        tid = _tls.tid = threading.get_ident() & 0x7FFFFFFF
    return tid


def _append(ev):
    """Append one raw event under the lock; emit the thread-name
    metadata row the first time a thread shows up."""
    global _dropped
    tid = ev["tid"]
    with _lock:
        if not _enabled:
            return
        if len(_events) >= _max_events:
            _dropped += 1
            return
        if tid not in _thread_meta_done:
            _thread_meta_done.add(tid)
            _events.append({
                "name": "thread_name", "ph": "M", "pid": _PID,
                "tid": tid,
                "args": {"name": threading.current_thread().name}})
        _events.append(ev)


# ---------------------------------------------------------------------------
# lifecycle
# ---------------------------------------------------------------------------

def enable(max_events=None, clear=True):
    """Turn span collection on (optionally bounding/clearing the
    buffer).  Safe to call when already enabled."""
    global _enabled, _max_events, _dropped, _epoch
    with _lock:
        if max_events is not None:
            _max_events = int(max_events)
        if clear:
            del _events[:]
            _thread_meta_done.clear()
            _dropped = 0
            _epoch = time.perf_counter()
        _enabled = True


def disable():
    global _enabled
    with _lock:
        _enabled = False


def is_enabled():
    return _enabled


def reset():
    """Drop every collected event (keeps the enabled state)."""
    global _dropped, _epoch
    with _lock:
        del _events[:]
        _thread_meta_done.clear()
        _dropped = 0
        _epoch = time.perf_counter()


class _TracingGuard:
    def __init__(self, max_events):
        self._max_events = max_events
        self._prev_max = None

    def __enter__(self):
        # scoped API: a guard-local bound must not leak into every
        # later enable() of the process (which would silently drop
        # their events once the small buffer fills)
        self._prev_max = _max_events
        enable(max_events=self._max_events, clear=True)
        return self

    def __exit__(self, *exc):
        global _max_events
        with _lock:
            _max_events = self._prev_max
        disable()
        return False


def tracing(max_events=None):
    """`with tracing(): ...` — collect spans for the body, then stop
    (events stay buffered for export)."""
    return _TracingGuard(max_events)


def events():
    """Snapshot of the raw event list (copies the list, not the
    dicts)."""
    with _lock:
        return list(_events)


def epoch():
    """The perf_counter() origin of event timestamps (re-based by
    enable(clear=True)/reset) — lets sibling exporters (obs.comm) put
    their tracks on the same timeline."""
    return _epoch


def event_count():
    """Current buffer length — a cheap bookmark for `events_since`
    (obs.comm's overlap report takes one instead of copying the whole
    buffer)."""
    with _lock:
        return len(_events)


def events_since(index):
    """Copy of the events appended after bookmark `index` (an earlier
    `event_count()` result).  A reset/clear since the bookmark leaves
    the buffer shorter than the bookmark, so the slice is empty — the
    window's events are gone and the caller's sample is lost."""
    with _lock:
        return list(_events[index:])


def dropped_events():
    with _lock:
        return _dropped


# ---------------------------------------------------------------------------
# spans
# ---------------------------------------------------------------------------

class _ProfilerSpan(TraceAnnotation):
    """What `span` returns while obs.trace is off: the profiler's
    annotation and nothing else."""

    __slots__ = ()

    def set(self, **args):
        """Attach/extend args after entry (e.g. a compile-hit flag
        only known at the end of the span)."""
        self.set_metadata(**args)
        return self


class _Span(_ProfilerSpan):
    """The profiler's annotation plus one in-memory event at exit."""

    __slots__ = ("name", "cat", "args", "_t0")

    def __init__(self, name, cat, args):
        super().__init__(name, **args)
        self.name = name
        self.cat = cat
        self.args = args

    def set(self, **args):
        self.args.update(args)
        return super().set(**args)

    def __enter__(self):
        super().__enter__()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        t0 = self._t0
        dur = time.perf_counter() - t0
        super().__exit__(*exc)
        ev = {"name": self.name, "cat": self.cat, "ph": "X",
              "ts": (t0 - _epoch) * 1e6, "dur": dur * 1e6,
              "pid": _PID, "tid": _tid()}
        if self.args:
            ev["args"] = self.args
        _append(ev)
        return False


def span(name, cat="paddle_tpu", **args):
    """Context manager around one nested region: always an annotation
    in the profiler's trace (recorded while a profiler session runs),
    and an in-memory event too while obs.trace is enabled.  `name` is a
    fixed `<layer>/<what>`; what varies goes into `args`.  A span of
    `cat="startup"` is kept on the start-up timeline instead, enabled
    or not.  (The test is one of identity, a third cheaper on the path
    every span of a steady-state step takes: `STARTUP` and the literal,
    which Python interns, are one object.)"""
    if cat is STARTUP:
        return _StartupSpan(name, args)
    if not _enabled:
        return _ProfilerSpan(name, **args)
    return _Span(name, cat, args)


def emit_span(name, t0_perf, dur_s, cat="paddle_tpu", args=None):
    """Record an already-measured region (t0 from time.perf_counter(),
    duration in seconds) — for callers that time once and feed both
    the tracer and an aggregate table (fluid.profiler.record_event).
    With `cat="startup"` it goes to the start-up timeline, enabled or
    not, and what this thread recorded there while the region ran
    becomes its children."""
    if cat == STARTUP:
        _startup_emit(name, t0_perf, dur_s, dict(args or {}))
        return
    if not _enabled:
        return
    ev = {"name": name, "cat": cat, "ph": "X",
          "ts": (t0_perf - _epoch) * 1e6, "dur": dur_s * 1e6,
          "pid": _PID, "tid": _tid()}
    if args:
        ev["args"] = dict(args)
    _append(ev)


def instant(name, cat="paddle_tpu", **args):
    """Mark a moment (thread-scoped instant event) — jit trace
    detections, drain signals, ..."""
    if not _enabled:
        return
    ev = {"name": name, "cat": cat, "ph": "i", "s": "t",
          "ts": _now_us(), "pid": _PID, "tid": _tid()}
    if args:
        ev["args"] = args
    _append(ev)


# ---------------------------------------------------------------------------
# the start-up timeline
# ---------------------------------------------------------------------------

def _startup_open():
    """This thread's open start-up events, outermost first."""
    stack = getattr(_tls, "startup", None)
    if stack is None:
        stack = _tls.startup = []
    return stack


def _startup_append(name, t0, dur, args):
    """One more event, under the one open on this thread; its index, or
    -1 where the list is full."""
    global _startup_dropped
    stack = _startup_open()
    parent = stack[-1] if stack else -1
    ev = {"name": name, "t0": t0, "dur": dur, "tid": _tid(),
          "thread": threading.current_thread().name,
          "parent": parent, "args": args}
    # a process that keeps compiling must not push a first run, which
    # is told at its end, off the list: the last places are not for
    # jit phases
    room = _STARTUP_MAX - (_STARTUP_KEPT_FREE
                           if name.startswith(_JIT_PHASE_PREFIX) else 0)
    with _lock:
        if len(_startup) >= room:
            _startup_dropped += 1
            return -1
        _startup.append(ev)
        _startup_children.append(0)
        if parent >= 0:
            _startup_children[parent] += 1
        return len(_startup) - 1


def _startup_emit(name, t0, dur, args):
    """A region measured after the fact.  The events this thread began
    inside it, under the event that is open now as it was then, ran
    inside the region: they become its children (a first run learns
    only at its end that it was one; an outer function's trace is told
    after the traces of the functions it calls).  One thread's events
    lie in the list in the order they began or were told, so the walk
    back ends at the first that began before the region.

    JAX tells a trace for every jitted function that a traced function
    calls and for every function a lowering rule traces, thousands of
    them for one step of a large model, each before the phase it ran
    inside: where the region is a jit phase, a childless phase at the
    list's end that lies inside it is taken off again, its time being
    the region's own, so that the list holds a function's phase
    once."""
    tid = _tid()
    if name.startswith(_JIT_PHASE_PREFIX):
        with _lock:
            while _startup:
                last = _startup[-1]
                if not last["name"].startswith(_JIT_PHASE_PREFIX) \
                        or last["tid"] != tid or last["t0"] < t0 \
                        or _startup_children[-1]:
                    break
                _startup.pop()
                _startup_children.pop()
                if last["parent"] >= 0:
                    _startup_children[last["parent"]] -= 1
    index = _startup_append(name, t0, dur, args)
    if index < 0:
        return
    with _lock:
        parent = _startup[index]["parent"]
        for i in range(index - 1, -1, -1):
            other = _startup[i]
            if other["tid"] != tid:
                continue
            if other["t0"] < t0:
                break
            if other["parent"] == parent:
                other["parent"] = index
                _startup_children[index] += 1
                if parent >= 0:
                    _startup_children[parent] -= 1


class _StartupSpan(_ProfilerSpan):
    """The profiler's annotation plus one event of the start-up
    timeline: it takes its place in the list as it begins, so that what
    it holds can name it as `parent`, and learns its duration at exit
    (`dur` is None while it is open)."""

    __slots__ = ("name", "args", "_t0", "_index")

    def __init__(self, name, args):
        super().__init__(name, **args)
        self.name = name
        self.args = args
        self._t0 = None

    def began(self, t0):
        """Count the span from `t0` (a `time.perf_counter()` read
        before the tracer could be imported)."""
        self._t0 = t0
        return self

    def set(self, **args):
        self.args.update(args)
        return super().set(**args)

    def __enter__(self):
        super().__enter__()
        if self._t0 is None:
            self._t0 = time.perf_counter()
        self._index = _startup_append(self.name, self._t0, None, self.args)
        if self._index >= 0:
            _startup_open().append(self._index)
        return self

    def __exit__(self, *exc):
        dur = time.perf_counter() - self._t0
        super().__exit__(*exc)
        if self._index >= 0:
            _startup_open().pop()
            _startup[self._index]["dur"] = dur
        return False


def startup_events():
    """Snapshot of the start-up timeline: one dict an event, `name`,
    `t0` and `dur` in seconds of `time.perf_counter()` (`dur` None while
    the event is open), `tid` and `thread`, `parent` (the index in this
    list of the start-up event that was open on that thread when it
    began, -1 for none) and `args`."""
    with _lock:
        return [dict(ev) for ev in _startup]


OUTSIDE = " (outside)"


def startup_summary(since=None, until=None, events=None):
    """Where the time to the first answer went: `{"events": {name:
    {"calls", "self_s"}}, "covered": seconds, "dropped": count}` over
    the events (`events`, else this process's timeline) that began in
    `[since, until)`.  Self seconds are an event's duration minus its
    children's, by `parent`; an event still open counts up to now.  A
    jit phase with no program event above it (a jit of the caller's
    own) is summed under its name + `" (outside)"`.  `covered` is the
    length of the union of the others inside the interval: what the
    program can account for."""
    with _lock:
        dropped = _startup_dropped
    if events is None:
        events = startup_events()
    now = time.perf_counter()
    durs = [ev["dur"] if ev["dur"] is not None
            else max(0.0, now - ev["t0"]) for ev in events]
    children = [0.0] * len(events)
    outside = []
    for ev, dur in zip(events, durs):
        parent = ev["parent"]
        if parent >= 0:
            children[parent] += dur
        # a phase under another event is where that event is (None
        # until the walk below has been up there); emit_span adopts,
        # so a parent may lie after its child
        phase = ev["name"].startswith(_JIT_PHASE_PREFIX)
        outside.append(None if phase and parent >= 0 else phase)
    for i in range(len(events)):
        chain = []
        while outside[i] is None:
            chain.append(i)
            i = events[i]["parent"]
        for j in chain:
            outside[j] = outside[i]
    by_name, spans = {}, []
    for ev, dur, below, out in zip(events, durs, children, outside):
        t0 = ev["t0"]
        if (since is not None and t0 < since) \
                or (until is not None and t0 >= until):
            continue
        row = by_name.setdefault(ev["name"] + (OUTSIDE if out else ""),
                                 {"calls": 0, "self_s": 0.0})
        row["calls"] += 1
        row["self_s"] += max(0.0, dur - below)
        if not out:
            spans.append((t0, t0 + dur if until is None
                          else min(t0 + dur, until)))
    covered, end = 0.0, float("-inf")
    for t0, t1 in sorted(spans):
        if t1 > end:
            covered += t1 - max(t0, end)
            end = t1
    return {"events": by_name, "covered": covered, "dropped": dropped}


# ---------------------------------------------------------------------------
# export
# ---------------------------------------------------------------------------

def to_chrome_trace():
    """The trace as a Chrome trace-event dict:
    `{"traceEvents": [...], "otherData": {...}}`."""
    with _lock:
        evs = list(_events)
        dropped = _dropped
        startup_dropped = _startup_dropped
    meta = [{"name": "process_name", "ph": "M", "pid": _PID, "tid": 0,
             "args": {"name": "paddle_tpu"}}]
    # the start-up timeline rides along on the same clock (an event
    # from before the epoch has a negative ts), each event with its
    # place in the list and its parent's, so that a reader of the file
    # can make `startup_summary`'s table (`obs_dump --startup FILE`)
    now = time.perf_counter()
    startup = [
        {"name": ev["name"], "cat": STARTUP, "ph": "X",
         "ts": (ev["t0"] - _epoch) * 1e6,
         "dur": (now - ev["t0"] if ev["dur"] is None else ev["dur"]) * 1e6,
         "pid": _PID, "tid": ev["tid"],
         "args": dict(ev["args"], startup_index=i,
                      startup_parent=ev["parent"])}
        for i, ev in enumerate(startup_events())]
    return {
        "traceEvents": meta + evs + startup,
        "displayTimeUnit": "ms",
        "otherData": {"producer": "paddle_tpu.obs.trace",
                      "dropped_events": dropped,
                      "startup_dropped_events": startup_dropped},
    }


def export_chrome_trace(path=None):
    """Serialize the trace; writes `path` (atomic tmp+rename) when
    given, returns the dict either way."""
    doc = to_chrome_trace()
    if path:
        tmp = str(path) + ".tmp"
        with open(tmp, "w") as f:
            json.dump(doc, f)
        import os

        os.replace(tmp, str(path))
    return doc
