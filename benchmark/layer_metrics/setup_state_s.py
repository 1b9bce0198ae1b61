"""Seconds of set-up the program spent making and placing its state: the
self seconds of `startup/trainer_init` (`ParallelTrainer.init` without the
start-up program's run), `startup/decoder_init`
(`ProgramDecoder.__init__`), `startup/state_place` (the `device_put` of a
trainer's state, a decoder's weights that cross from the host) and
`startup/load` (`fluid.io`'s loads from disk) on the program's start-up
timeline.  Prints the bytes placed and the GB/s of each placement."""

from benchmark.reduce import setup_timeline

LAYER = "executor"
MOVES = "setup_s"
UNIT = "s"
SOURCE = "program_counter"


def read(run):
    found = setup_timeline.cut(run)
    if found is None:
        return None
    for _, ev in setup_timeline.in_setup(found, ("state_place", "load")):
        nbytes = ev["args"].get("bytes", 0)
        print("set-up, %s: %.3f s%s" % (
            setup_timeline.describe(ev), ev["dur"],
            ", %.3f GB/s" % (nbytes / ev["dur"] / 1e9)
            if nbytes and ev["dur"] else ""), flush=True)
    return setup_timeline.self_seconds(found, setup_timeline.STATE)
