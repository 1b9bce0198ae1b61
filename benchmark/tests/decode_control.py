"""The control of a generation cell's `correct`, at the cell's own size
on the chip or at a toy size under pytest (test_decode_cell.py).

    python3 benchmark/tests/decode_control.py --workload gpt2m-decode \
        --seeds 11,12,13 [--lower serve_dtype=float8_e4m3fn] \
        [--lower weights.dtype=float8_e4m3fn] [--set batch=32] \
        [--pool-index I] [--search-path DIR]

For every seed, in one process: the system serves one call of the cell
(the timed path at the timed sizes, no window), and the plain reference
then reads, over every row of that call, the numbers `correct` compares.
Then the same for every `--lower`: the program's own path in the
precision below the one the cell states (a float8 key/value cache, float8
weights), switched on by that one key of the workload, its served tokens
held to the same reference of the cell as stated.  The limits in the
workload file lie between the sound line and the lowered lines this
prints; the benchmark's own runs never run it.  `--set` changes a key of
the workload for the sound and the lowered calls alike (a sizing trial,
another draw of the weights).
"""

import argparse
import copy
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(os.path.dirname(HERE)))

from benchmark import harness  # noqa: E402


def changed(workload, assignment):
    """A copy of `workload` with `a.b=<json or text>` set."""
    key, _, text = assignment.partition("=")
    try:
        value = json.loads(text)
    except ValueError:
        value = text
    out = copy.deepcopy(workload)
    at = out
    *groups, last = key.split(".")
    for group in groups:
        at = at[group]
    at[last] = value
    return out


def read(lookup, workload, seed, devices, peaks, lower=None, index=0):
    """{"gap_max", "gap_mean", "not_first_share", "tokens", "distinct",
    "memory_peak_bytes"} of one call of the cell `workload` at `seed`, served with the
    assignment `lower` switched on and compared as the cell states."""
    config = lookup.json("configs", workload["config"])

    def a_run(cell):
        return harness.Run(cell, config, seed, 0.0, False, lookup, devices,
                           peaks, harness.SetupClock(time.perf_counter()),
                           harness.CompileClock())

    driver = lookup.module("drivers", workload["driver"])
    model = lookup.module("models", workload["builder"])
    pool = model.prompts(config, workload, seed)
    served = a_run(changed(workload, lower) if lower else workload)
    generate = driver.serve(served, model)
    tokens, lengths = generate(pool[index], workload["gen_len"])
    peak = harness.memory_peak_bytes(devices)
    del generate
    got = driver.compare(a_run(workload), model, pool,
                         (index, tokens, lengths))
    got["memory_peak_bytes"] = peak     # a sizing trial reads it
    return got


def main(argv=None):
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seeds", required=True)
    p.add_argument("--lower", action="append", default=[])
    p.add_argument("--set", action="append", default=[], dest="sets")
    p.add_argument("--pool-index", type=int, default=0)
    p.add_argument("--search-path", action="append", default=[])
    args = p.parse_args(argv)
    lookup = harness.Lookup(args.search_path)
    workload = lookup.json("workloads", args.workload)
    workload["name"] = args.workload
    for assignment in args.sets:
        workload = changed(workload, assignment)
    devices, peaks = harness.require_devices(workload["chips"], lookup)
    harness.place_compile_cache()
    for seed in (int(s) for s in args.seeds.split(",")):
        for lower in [None] + args.lower:
            got = read(lookup, workload, seed, devices, peaks, lower,
                       args.pool_index)
            got.update(seed=seed, lower=lower, set=args.sets,
                       pool_index=args.pool_index)
            print(json.dumps(got), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
