"""Seconds of set-up inside the import of the program's package: the
`startup/import` event of its start-up timeline (`paddle_tpu/__init__.py`,
first line to last) with its children (`startup/import_fluid`,
`startup/import_v2`; `startup/import_kernels` where the kernels' package
is imported at import time).  JAX's own import is inside it only where the
caller had not imported JAX before; the benchmark has.  Prints the
children."""

from benchmark.reduce import setup_timeline

LAYER = "program"
MOVES = "setup_s"
UNIT = "s"
SOURCE = "program_counter"


def read(run):
    found = setup_timeline.cut(run)
    if found is None:
        return None
    print("set-up, the package's import: %s" % ", ".join(
        "%s %.3f s" % (name, setup_timeline.self_seconds(found, [name]))
        for name in setup_timeline.IMPORT
        if setup_timeline.PREFIX + name in found.rows), flush=True)
    return setup_timeline.self_seconds(found, setup_timeline.IMPORT)
