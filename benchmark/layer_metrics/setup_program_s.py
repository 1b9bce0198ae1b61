"""Seconds of set-up the program spent on its IR in Python: the self
seconds of `startup/program_backward` (`append_backward`),
`startup/program_optimize` (`Optimizer.minimize` without the backward),
`startup/functional_program` (`FunctionalProgram.__init__`) and
`startup/executor_plan` (a plan's miss: verification, the rewrite passes,
the plan) on the program's start-up timeline.  The forward build
(`fluid.layers` calls from a model's builder) has no entry point in the
program and is not in it.  Prints each with its calls."""

from benchmark.reduce import setup_timeline

LAYER = "program"
MOVES = "setup_s"
UNIT = "s"
SOURCE = "program_counter"


def read(run):
    found = setup_timeline.cut(run)
    if found is None:
        return None
    print("set-up, the IR's work in Python: %s" % (", ".join(
        "%s x%d %.3f s" % (name, found.rows[setup_timeline.PREFIX + name]
                           ["calls"],
                           setup_timeline.self_seconds(found, [name]))
        for name in setup_timeline.PROGRAM
        if setup_timeline.PREFIX + name in found.rows) or "none"),
          flush=True)
    return setup_timeline.self_seconds(found, setup_timeline.PROGRAM)
