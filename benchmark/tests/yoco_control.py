"""The controls of the decoder-hybrid-decoder cell's `correct`, at the
cell's own size on the chip or at a toy size under pytest
(test_phi4flash_cell.py).

    python3 benchmark/tests/yoco_control.py \
        --workload phi4flash-turn-16k --seeds 11,12 \
        [--lower serve_dtype=float8_e4m3fn] [--lower state_dtype=bfloat16] \
        [--lower window=256] [--lower control.subtract=false] \
        [--lower control.memory_after_gate=true] \
        [--lower control.cross_before_write=true] [--no-sound] \
        [--set weights.q_gain=4] [--search-path DIR]

For every seed, in one process: set-up makes the reference's session
once (the float32 states of every document), the system serves one call
of the cell from it, and the plain reference then reads, over the
checked rows of that call and the probes of its last step, the numbers
`correct` compares (decode_yoco.compare's); then the same for every
`--lower`, from the same reference session laid out anew: the program's
own path with that one key of the workload changed, held to the
reference of the cell as stated.  Keys and values kept in float8 (the
session handed in rounds to it too); the scan's state carried in
bfloat16; a ring of 256 slots in place of 512; and the builder's three
wrong wirings: the second attention map not subtracted, the memory
taken after layer 16's gate, the cross layers reading the cache as it
stood before the step's write.  A JSON line a reading.  The limits in
the workload file lie between the sound lines and the controls' lines
this prints; the benchmark's own runs never run it, and it exits 0
whatever it reads.
"""

import argparse
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(os.path.dirname(HERE)))

from benchmark import harness  # noqa: E402
from benchmark.tests import decode_control  # noqa: E402


def readings(lookup, workload, seed, devices, peaks, controls):
    """One {decode_yoco.compare's numbers, "memory_peak_bytes"} a
    control (None: the cell as stated), all from one reference
    session."""
    config = lookup.json("configs", workload["config"])

    def a_run(cell):
        return harness.Run(cell, config, seed, 0.0, False, lookup, devices,
                           peaks, harness.SetupClock(time.perf_counter()),
                           harness.CompileClock())

    driver = lookup.module("drivers", workload["driver"])
    model = lookup.module("models", workload["builder"])
    pool = model.prompts(config, workload, seed)
    documents = model.documents(config, workload, seed)
    stated = a_run(workload)
    made, inputs = driver.reference_session(stated, model, documents)
    for control in controls:
        served = a_run(decode_control.changed(workload, control)
                       if control else workload)
        init = driver.lay_out(served, model, made, documents.shape[1])
        generate = driver.serve(served, model, init,
                                driver.build(served, model))
        del init
        call = (0,) + generate(pool[0], workload["gen_len"])
        peak = harness.memory_peak_bytes(devices)
        del generate
        got = driver.compare(stated, model, documents, pool, call, inputs)
        got["memory_peak_bytes"] = peak     # a sizing trial reads it
        yield control, got


def main(argv=None):
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seeds", required=True)
    p.add_argument("--lower", action="append", default=[])
    p.add_argument("--no-sound", action="store_true",
                   help="the controls alone")
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
    controls = ([] if args.no_sound else [None]) + args.lower
    for seed in (int(s) for s in args.seeds.split(",")):
        for control, got in readings(lookup, workload, seed, devices, peaks,
                                     controls):
            got.update(seed=seed, control=control, set=args.sets)
            print(json.dumps(got), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
