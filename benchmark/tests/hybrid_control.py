"""The controls of the hybrid cell's `correct`, at the cell's own size on
the chip or at a toy size under pytest (test_ling3_cell.py).

    python3 benchmark/tests/hybrid_control.py --workload ling3-decode-ep16 \
        --seeds 11,12 [--control gate=head] ... [--all] [--search-path DIR]

What benchmark/tests/state_control.py is for the state cell, and that
file's code (its `read`, `refused`, `parsed`): for every seed, in one
process, the system serves one call of the cell, the plain reference
reads the numbers `correct` compares, and the same served call is then
held to the reference **made wrong in one named way**, once a
`--control` (benchmark/reference/ling3_flash.py lists them).  At least
one limit must refuse each; the limits in the workload file lie between
the sound line and the controls' lines this prints.

`--all`: the gate averaged over a head's channels (what the rule under a
gate a head computes: the control that tells KDA from Gated DeltaNet),
the lower bound dropped, the state rounded to bfloat16 after every
position, beta taken as 1, the rule's read `S^T k` left out, the tail
not carried across the prefill/decode boundary, the head-wise gates left
out, the latent's norm left out, rotation over all of a head's query and
key values, a token's eighth expert dropped.
"""

import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(os.path.dirname(HERE)))

from benchmark import harness  # noqa: E402
from benchmark.tests import state_control  # noqa: E402


def controls_of(config, workload):
    """{spelling: the reference's `control`} of `--all`."""
    whole = config["qk_nope_head_dim"] + config["qk_rope_head_dim"]
    return {
        "gate=head": {"gate": "head"},
        "floor=false": {"floor": False},
        "state=bfloat16": {"state": "bfloat16"},
        "beta=1": {"beta": 1},
        "read=false": {"read": False},
        "tail_cut=%d" % workload["prompt_len"]:
            {"tail_cut": workload["prompt_len"]},
        "out_gate=false": {"out_gate": False},
        "latent_norm=false": {"latent_norm": False},
        "rotary=%d" % whole: {"rotary": whole},
        "drop=true": {"drop": True},
    }


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
    controls.update({c: state_control.parsed(c) for c in args.control})
    ok = True
    for seed in (int(s) for s in args.seeds.split(",")):
        for control, got in state_control.read(
                lookup, workload, seed, devices, peaks, controls):
            over = state_control.refused(got, workload["correct"])
            ok &= bool(over) == (control is not None)
            got.update(seed=seed, control=control, refused_by=over)
            print(json.dumps(got), flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
