"""The controls of the sparse key/value cell's `correct`, at the cell's
own size on the chip or at a toy size under pytest (test_keye_cell.py).

    python3 benchmark/tests/sparse_control.py \
        --workload keye-turn-64k-ep8 --seeds 11,12 \
        [--lower serve_dtype=float8_e4m3fn] \
        [--lower index_dtype=float8_e4m3fn] [--lower index_topk=1024] \
        [--lower weights.routed_mantissa_bits=3] \
        [--lower rope_delta_zero=true] \
        [--lower 'session_control={"swap_hw":true}'] [--recent] \
        [--set weights.qk_gain=4] [--search-path DIR]

benchmark/tests/session_control.py's loop (for every seed, in one
process: set-up makes the session, the system serves one call of the
cell from it, and the plain reference then reads, over the checked rows
of that call and the probes of its last step, the numbers `correct`
compares; then the same for every control).  A `--lower` is the
program's own path with that one key of the workload changed, held to
the reference of the cell as stated: keys and values cached in float8
(the session handed in rounds to it too), the chooser's keys cached in
float8, half as many slots chosen, the held experts' weights at three
mantissa bits, `rope_delta` handed in as 0 (the slot taken for the
position), a session whose images were laid out with the height and the
width sections exchanged.  `--recent` is session_control.py's: the
chooser's scores are thrown away and every step attends the most recent
`topk` slots.  The limits in the workload file lie between the sound
line and the controls' lines this prints; the benchmark's own runs never
run it.
"""

import argparse
import contextlib
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(os.path.dirname(HERE)))

from benchmark import harness  # noqa: E402
from benchmark.tests import decode_control  # noqa: E402

from benchmark.tests.session_control import (  # noqa: E402
    RECENT, most_recent_slots_chosen)


def read(lookup, workload, seed, devices, peaks, control=None):
    """What `correct` compares (decode_sparse.compare's numbers, and
    "memory_peak_bytes" while serving) of one call of the cell `workload`
    at `seed`, served under `control` (a `--lower` assignment, RECENT or
    None) and compared as the cell states."""
    config = lookup.json("configs", workload["config"])

    def a_run(cell):
        return harness.Run(cell, config, seed, 0.0, False, lookup, devices,
                           peaks, harness.SetupClock(time.perf_counter()),
                           harness.CompileClock())

    driver = lookup.module("drivers", workload["driver"])
    model = lookup.module("models", workload["builder"])
    pool = model.prompts(config, workload, seed)
    documents = model.documents(config, workload, seed)
    seen = model.images(config, workload, seed)
    lowered = control not in (None, RECENT)
    served = a_run(decode_control.changed(workload, control) if lowered
                   else workload)
    init, inputs = driver.make_session(served, model, documents, seen)
    with most_recent_slots_chosen() if control == RECENT \
            else contextlib.nullcontext():
        generate = driver.serve(served, model, init,
                                driver.build(served, model))
        call = (0,) + generate(pool[0], workload["gen_len"])
    peak = harness.memory_peak_bytes(devices)
    del generate, init
    got = driver.compare(a_run(workload), model, documents, seen, pool,
                         call, inputs)
    got["memory_peak_bytes"] = peak     # a sizing trial reads it
    return got


def main(argv=None):
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seeds", required=True)
    p.add_argument("--lower", action="append", default=[])
    p.add_argument("--recent", action="store_true")
    p.add_argument("--set", action="append", default=[], dest="sets")
    p.add_argument("--search-path", action="append", default=[])
    args = p.parse_args(argv)
    lookup = harness.Lookup(args.search_path)
    workload = lookup.json("workloads", args.workload)
    workload["name"] = args.workload
    for assignment in args.sets:
        workload = decode_control.changed(workload, assignment)
    devices, peaks = harness.require_devices(workload["chips"], lookup)
    harness.place_compile_cache()
    controls = [None] + args.lower + ([RECENT] if args.recent else [])
    for seed in (int(s) for s in args.seeds.split(",")):
        for control in controls:
            got = read(lookup, workload, seed, devices, peaks, control)
            got.update(seed=seed, control=control, set=args.sets)
            print(json.dumps(got), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
