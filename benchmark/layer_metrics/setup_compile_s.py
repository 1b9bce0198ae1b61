"""Seconds inside JAX's backend compile call during set-up: compiling on
a cold cache, loading from it on a warm one."""

LAYER = "compile_cache"
MOVES = "setup_s"
UNIT = "s"
SOURCE = "program_counter"


def read(run):
    return run.facts.get("setup_compile_s")
