"""SDAR-30B-A3B-Chat's pipeline stage against its plain float32 reference
at the published widths, from one command: the cached step Program of
benchmark/models/sdar_decode.py (the Qwen3-MoE block under a
block-causal mask of 4, all 128 experts eight a token, the logits of
every position fed) driven through `fluid.ProgramDecoder.diffuse` from
empty caches at the cell's own size (`--rows` rows x (256 prompt + 768
generated), blocks of 4, 4 denoising passes each and a commit that
rides on the next block's first), and the
reference's replay of that call's own trajectory
(benchmark/reference/sdar_moe.py, a layer at a time; what `correct`
compares in the cell: benchmark/drivers/decode_diffusion.py `compare`).

    chiprun --timeout 1500 -- python scripts/sdar_check.py --seeds 1,2,3
    chiprun --timeout 1800 -- python scripts/sdar_check.py --seeds 1 \
        --all-controls
    python scripts/sdar_check.py --workload sdar-tiny-diffuse \
        --search-path benchmark/tests/fixture --all-controls   # on the CPU

Numbers, a seed, over every denoising pass of a seeded 16 of the first 2
rows' 192 blocks: `gap_mean` and `not_first_share` (by how much the
reference's logit of a fixed token lies under the reference's best at
the positions the pass fixed, and how often it is not the best),
`conf_off` (|ln the program's confidence - ln the reference's
probability of the same token| in the mean), `other_position_share` (the
share of passes that fixed another position than the masked one the
reference ranks first for the same input) and `kv_off` (the first
layer's keys and values as committed against the reference's whole
forward of the final sequence).  The limits are the workload file's
(`correct`, with the readings they were set from).  Exit code 1 when a
number is outside its limit.  `--control key=value`
(benchmark/reference/sdar_moe.py lists them) holds the served call to a
reference made wrong in that way: it must exit 1.  `--all-controls` runs
the sound comparison and the four controls of
benchmark/tests/diffusion_control.py on the same served call, in one
process, and exits 1 unless the sound one passes and every control is
refused.  About a minute and a half a seed on the chip, a minute more a
control.
"""

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

NUMBERS = ("gap_mean", "not_first_share", "conf_off",
           "other_position_share", "kv_off")


def main(argv=None):
    from benchmark import harness
    from benchmark.tests import diffusion_control, state_control

    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", default="sdar-diffuse-pp8")
    p.add_argument("--seeds", default="1")
    p.add_argument("--rows", type=int, default=0,
                   help="rows of the call (default: the workload's batch)")
    p.add_argument("--control", action="append", default=[])
    p.add_argument("--all-controls", action="store_true")
    p.add_argument("--search-path", action="append", default=[])
    args = p.parse_args(argv)
    lookup = harness.Lookup(args.search_path)
    workload = lookup.json("workloads", args.workload)
    workload["name"] = args.workload
    if args.rows:
        workload["batch"] = args.rows
    config = lookup.json("configs", workload["config"])
    devices, peaks = harness.require_devices(workload["chips"], lookup)
    harness.place_compile_cache()
    controls = diffusion_control.controls_of(config, workload) \
        if args.all_controls else {}
    controls.update({c: state_control.parsed(c) for c in args.control})
    limits = workload["correct"]
    print("limits: %s" % ", ".join("%s %g" % (n, limits[n])
                                   for n in NUMBERS), flush=True)
    sound = refused = True
    for seed in (int(s) for s in args.seeds.split(",")):
        for control, got in state_control.read(lookup, workload, seed,
                                               devices, peaks, controls):
            over = state_control.refused(got, limits)
            if control is None:
                sound &= not over
            else:
                refused &= bool(over)
            print("seed %d %-28s %s  %s"
                  % (seed, control or "sound",
                     " ".join("%s %.5g" % (n, got[n]) for n in NUMBERS),
                     "refused by " + ", ".join(over) if over else "passes"),
                  flush=True)
            print(json.dumps(dict(got, seed=seed, control=control,
                                  refused_by=over)), flush=True)
    if args.all_controls:
        return 0 if sound and refused else 1
    # a number outside its limit, the sound comparison's or a control's
    return 0 if sound and not (controls and refused) else 1


if __name__ == "__main__":
    sys.exit(main())
