"""Programs the persistent compilation cache did not have during set-up.
0 on every run of a cell but the first in a checkout."""

LAYER = "compile_cache"
MOVES = "setup_s"
UNIT = "count"
SOURCE = "program_counter"


def read(run):
    return run.facts.get("setup_cache_misses")
