"""Of `setup_s`, the share that lies under an event of the program's
start-up timeline (`obs.trace.startup_summary`'s `covered`: the union of
the events that began in set-up, a jit of the caller's own left out): how
much of set-up the program can account for.

Prints the self seconds of the jit phases under a program event (with the
four groups of `setup_import_s`, `setup_program_s`, `setup_state_s` and
`setup_first_run_s` and with `setup_outside_program_s` they add up to
`setup_s`), those of the caller's own jits, any event of another name, and
every start-up event that began at or after the window's first instant
with its offset from it: what `compiles_in_window` counts, by name and
function (in a traced run the functional path's build after the windows
shows here too, and is expected, and so do a generation cell's check and
its reference; jit phases are summed a function and parent, at the
first one's offset)."""

from benchmark.reduce import setup_timeline

LAYER = "program"
MOVES = "setup_s"
UNIT = "%"
SOURCE = "program_counter"


def read(run):
    found = setup_timeline.cut(run)
    if found is None:
        return None
    print("set-up, jit phases under a program event: %s; of the caller's "
          "own: %s" % tuple(
              " ".join("%s %.3f s" % (name[len("jit_"):],
                                      setup_timeline.self_seconds(
                                          found, [name], suffix))
                       for name in setup_timeline.JIT)
              for suffix in ("", " (outside)")), flush=True)
    known = {setup_timeline.PREFIX + name + suffix
             for name in setup_timeline.IMPORT + setup_timeline.PROGRAM
             + setup_timeline.STATE + setup_timeline.FIRST_RUN
             + setup_timeline.JIT for suffix in ("", " (outside)")}
    other = sorted(set(found.rows) - known)
    if other:
        print("set-up, events no reader sums: %s" % ", ".join(
            "%s %.3f s" % (name, found.rows[name]["self_s"])
            for name in other), flush=True)
    print("start-up timeline: %d events began in set-up, %d at or after the "
          "window's first instant: %s"
          % (sum(row["calls"] for row in found.rows.values()),
             sum(ev["t0"] >= found.until for ev in found.events),
             "; ".join(_late(found)) or "none"), flush=True)
    return 100.0 * found.covered / found.setup_s


def _late(found):
    """A line an event that began at or after the window's first instant,
    in the order they began: the program's events one by one, the jit
    phases summed a function and parent (a reference's check runs
    hundreds of jits of the caller's own; a backward pass traces an op's
    shape inference hundreds of times), at the offset of the first."""
    lines, phases = [], {}
    for index, ev in enumerate(found.events):
        if ev["t0"] < found.until:
            continue
        offset = ev["t0"] - found.until
        if not ev["name"].startswith(setup_timeline.PREFIX + "jit_"):
            lines.append((offset, "+%.3f s %s %.3f s" % (
                offset, setup_timeline.describe(ev), ev["dur"] or 0.0)))
            continue
        key = ev["parent"], ev["args"].get("fun_name", "")
        if key not in phases:
            phases[key] = [0, 0.0]
            lines.append((offset, key))
        phases[key][0] += 1
        phases[key][1] += ev["dur"]
    return [line if line not in phases else
            "+%.3f s %s %s, %d phase(s) %.3f s" % (
                offset,
                "the caller's own jit of" if line[0] < 0
                else setup_timeline.describe(found.events[line[0]])
                + ": jit of", line[1], *phases[line])
            for offset, line in sorted(lines, key=lambda item: item[0])]
