"""Set-up as the program saw it: the start-up timeline `paddle_tpu.obs.trace`
keeps itself (`startup_events`, `startup_summary`), cut to one run's set-up,
from the start of the process (`run.clock.process_start`) to the first
instant of the measured window (`run.window_start`).  The timeline is on
`time.perf_counter()`, as both of those are, so nothing is shifted.

Six readers split `setup_s` by it: the package's import
(`setup_import_s`), the IR's work in Python (`setup_program_s`), the state's
way to the device (`setup_state_s`), the first runs without their jit
phases (`setup_first_run_s`), how much of set-up lies under any event
(`setup_named_share`) and what lies under none (`setup_outside_program_s`).
The groups below and the jit phases under a program event (which
`setup_trace_lower_s`, `decode_trace_lower_s` and `setup_compile_s` keep
reading from the counters) are every name the program gives, so the four
groups' self seconds, those phases' and `setup_outside_program_s` add up to
`setup_s` where one thread did the work.

A program without the timeline (one from before it existed) gives every
reader None, and so does a run in whose set-up no event began.
"""

import collections

PREFIX = "startup/"
IMPORT = ("import", "import_fluid", "import_v2", "import_kernels")
PROGRAM = ("program_backward", "program_optimize", "functional_program",
           "executor_plan")
STATE = ("trainer_init", "decoder_init", "state_place", "load")
FIRST_RUN = ("executor_first_run", "trainer_first_step", "decoder_build")
JIT = ("jit_trace", "jit_lower", "jit_compile")

Cut = collections.namedtuple("Cut", "events rows covered setup_s since until")


def cut(run):
    """The timeline of `run`'s set-up (kept on the run: six readers ask),
    or None where there is none."""
    if not hasattr(run, "_setup_timeline"):
        run._setup_timeline = _cut(run)
    return run._setup_timeline


def _cut(run):
    from paddle_tpu.obs import trace

    if not hasattr(trace, "startup_summary") or run.window_start is None:
        return None
    since, until = run.clock.process_start, run.window_start
    summary = trace.startup_summary(since=since, until=until)
    if not summary["events"]:
        return None
    if summary["dropped"]:
        print("start-up timeline: %d event(s) dropped, the list was full"
              % summary["dropped"], flush=True)
    return Cut(trace.startup_events(), summary["events"],
               summary["covered"], until - since, since, until)


def self_seconds(found, names, suffix=""):
    """The self seconds of the events of `names` in set-up."""
    return sum(found.rows.get(PREFIX + name + suffix, {"self_s": 0.0})["self_s"]
               for name in names)


def in_setup(found, names):
    """[(index, event)] of the events of `names` that began in set-up."""
    wanted = {PREFIX + name for name in names}
    return [(i, ev) for i, ev in enumerate(found.events)
            if ev["name"] in wanted and found.since <= ev["t0"] < found.until]


def children(found, index):
    return [ev for ev in found.events if ev["parent"] == index]


def phases_by_function(events):
    """{fun_name: {phase: seconds}} of the jit phases among `events`."""
    by_function = collections.defaultdict(lambda: collections.defaultdict(float))
    for ev in events:
        if ev["name"].startswith(PREFIX + "jit_"):
            by_function[ev["args"].get("fun_name", "")][
                ev["name"][len(PREFIX + "jit_"):]] += ev["dur"]
    return by_function


def describe(ev):
    """An event's name without the prefix and its args, on one line."""
    args = " ".join("%s=%s" % item for item in sorted(ev["args"].items()))
    return ev["name"][len(PREFIX):] + (" [%s]" % args if args else "")
