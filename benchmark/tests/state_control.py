"""The controls of the state cell's `correct`, at the cell's own size on
the chip or at a toy size under pytest (test_qwen3next_cell.py).

    python3 benchmark/tests/state_control.py --workload qwen3next-decode-ep16 \
        --seeds 11,12 [--control state=bfloat16] ... [--all] \
        [--search-path DIR]

For every seed, in one process: the system serves one call of the cell,
and the plain reference then reads, over the checked rows of that call
and the probes of its last step, the numbers `correct` compares
(decode_state.compare's); then the same served call is held to the
reference **made wrong in one named way**, once a `--control`, switched
by one key of the workload (`control`, which the driver hands the
reference as `cfg["control"]`: benchmark/reference/qwen3_next.py lists
them).  A program that computed what the wrong reference computes would
read, against the sound reference, what the sound program reads against
the wrong one: at least one limit must refuse each.  The limits in the
workload file lie between the sound line and the controls' lines this
prints; the benchmark's own runs never run it.

`--all`: the state rounded to bfloat16 after every position, the state
zeroed after every position, the decay left out, beta taken as 1, the
rule's read `S^T k` left out, the tail not carried across the
prefill/decode boundary, rotation over the whole head, the attention
gate left out, the shared gate left out, a token's tenth expert dropped.
"""

import argparse
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(os.path.dirname(HERE)))

from benchmark import harness  # noqa: E402


def controls_of(config, workload):
    """{spelling: the reference's `control`} of `--all`."""
    return {
        "state=bfloat16": {"state": "bfloat16"},
        "state=zero": {"state": "zero"},
        "decay=false": {"decay": False},
        "beta=1": {"beta": 1},
        "read=false": {"read": False},
        "tail_cut=%d" % workload["prompt_len"]:
            {"tail_cut": workload["prompt_len"]},
        "rotary=%d" % config["head_dim"]: {"rotary": config["head_dim"]},
        "attn_gate=false": {"attn_gate": False},
        "shared_gate=false": {"shared_gate": False},
        "drop=true": {"drop": True},
    }


def parsed(spelling):
    key, _, value = spelling.partition("=")
    try:
        return {key: json.loads(value)}
    except ValueError:
        return {key: value}


def refused(got, limits):
    """The limits a reading passes."""
    return sorted(name for name in set(limits) - {"why"}
                  if got[name] > limits[name])


def read(lookup, workload, seed, devices, peaks, controls, index=0):
    """[(control or None, what `correct` compares)] of one call of the
    cell `workload` at `seed`: served once, compared as the cell states
    and then under each of `controls` ({spelling: control})."""
    config = lookup.json("configs", workload["config"])

    def a_run(cell):
        return harness.Run(cell, config, seed, 0.0, False, lookup, devices,
                           peaks, harness.SetupClock(time.perf_counter()),
                           harness.CompileClock())

    driver = lookup.module("drivers", workload["driver"])
    model = lookup.module("models", workload["builder"])
    pool = model.prompts(config, workload, seed)
    generate = driver.serve(a_run(workload), model)
    call = (index,) + generate(pool[index], workload["gen_len"])
    peak = harness.memory_peak_bytes(devices)
    del generate
    for spelling, control in [(None, None)] + list(controls.items()):
        cell = workload if control is None \
            else dict(workload, control=control)
        got = driver.compare(a_run(cell), model, pool, call)
        got["memory_peak_bytes"] = peak
        yield spelling, got


def main(argv=None):
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seeds", required=True)
    p.add_argument("--control", action="append", default=[])
    p.add_argument("--all", action="store_true")
    p.add_argument("--search-path", action="append", default=[])
    args = p.parse_args(argv)
    lookup = harness.Lookup(args.search_path)
    workload = lookup.json("workloads", args.workload)
    workload["name"] = args.workload
    config = lookup.json("configs", workload["config"])
    devices, peaks = harness.require_devices(workload["chips"], lookup)
    harness.place_compile_cache()
    controls = controls_of(config, workload) if args.all else {}
    controls.update({c: parsed(c) for c in args.control})
    ok = True
    for seed in (int(s) for s in args.seeds.split(",")):
        for control, got in read(lookup, workload, seed, devices, peaks,
                                 controls):
            over = refused(got, workload["correct"])
            ok &= bool(over) == (control is not None)
            got.update(seed=seed, control=control, refused_by=over)
            print(json.dumps(got), flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
