"""The controls of the share cell's `correct`, at the cell's own size on
the chip or at a toy size under pytest (test_pangu_cell.py).

    python3 benchmark/tests/share_control.py --workload pangu-decode-ep16 \
        --seeds 11,12 [--lower serve_dtype=float8_e4m3fn] \
        [--lower weights.routed_mantissa_bits=3] [--drop] \
        [--set weights.q_gain=6] [--search-path DIR]

benchmark/tests/decode_control.py's loop (for every seed, in one
process: the system serves one call of the cell, and the plain reference
then reads, over the checked rows of that call and the probes of its
last step, the numbers `correct` compares; then the same for every
`--lower`, the program's own path in
the precision below the one the cell states, switched on by that one key
of the workload: a float8 latent cache, the held experts' weights
rounded to float8), with one control more, `--drop`: every token's last
*held* expert is left out where the expert op is handed its assignments
(the chosen index replaced by one no chip holds), so the token's routed
sum lacks one term; what `olmoe-train-4k`'s loss cannot see.  The limits
in the workload file lie between the sound line and the controls' lines
this prints; the benchmark's own runs never run it.
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

DROP = "a token's last held expert dropped"


@contextlib.contextmanager
def last_held_expert_dropped():
    """`moe_experts` with every token's last assignment to a held expert
    turned into one to an expert nobody holds."""
    import jax.numpy as jnp
    from paddle_tpu.ops import registry

    info = registry.get_op_info("moe_experts")
    real = info.kernel

    def dropped(ctx, ins, attrs):
        idx = ins["TopIdx"][0]
        first = int(attrs.get("first_expert", 0))
        held = (idx >= first) & (idx < first + ins["WGate"][0].shape[0])
        k = idx.shape[1]
        # the last column that is held, per token
        last = k - 1 - jnp.argmax(held[:, ::-1], axis=1)
        drop = held & (jnp.arange(k)[None, :] == last[:, None])
        ins = dict(ins, TopIdx=[jnp.where(drop, -1, idx)])
        return real(ctx, ins, attrs)

    info.kernel = dropped
    try:
        yield
    finally:
        info.kernel = real


def read(lookup, workload, seed, devices, peaks, control=None, index=0):
    """What `correct` compares (decode_share.compare's numbers, and
    "memory_peak_bytes" while serving) of one call of the cell `workload`
    at `seed`, served under `control` (a `--lower` assignment, DROP or
    None) and compared as the cell states: decode_control.read, for a
    `generate` that gives the probes too."""
    config = lookup.json("configs", workload["config"])

    def a_run(cell):
        return harness.Run(cell, config, seed, 0.0, False, lookup, devices,
                           peaks, harness.SetupClock(time.perf_counter()),
                           harness.CompileClock())

    driver = lookup.module("drivers", workload["driver"])
    model = lookup.module("models", workload["builder"])
    pool = model.prompts(config, workload, seed)
    lowered = control not in (None, DROP)
    served = a_run(decode_control.changed(workload, control) if lowered
                   else workload)
    with last_held_expert_dropped() if control == DROP \
            else contextlib.nullcontext():
        generate = driver.serve(served, model)
        call = (index,) + generate(pool[index], workload["gen_len"])
    peak = harness.memory_peak_bytes(devices)
    del generate
    got = driver.compare(a_run(workload), model, pool, call)
    got["memory_peak_bytes"] = peak     # a sizing trial reads it
    return got


def main(argv=None):
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seeds", required=True)
    p.add_argument("--lower", action="append", default=[])
    p.add_argument("--drop", action="store_true")
    p.add_argument("--no-sound", action="store_true")
    p.add_argument("--set", action="append", default=[], dest="sets")
    p.add_argument("--pool-index", type=int, default=0)
    p.add_argument("--search-path", action="append", default=[])
    args = p.parse_args(argv)
    lookup = harness.Lookup(args.search_path)
    workload = lookup.json("workloads", args.workload)
    workload["name"] = args.workload
    for assignment in args.sets:
        workload = decode_control.changed(workload, assignment)
    devices, peaks = harness.require_devices(workload["chips"], lookup)
    harness.place_compile_cache()
    controls = ([] if args.no_sound else [None]) + args.lower \
        + ([DROP] if args.drop else [])
    for seed in (int(s) for s in args.seeds.split(",")):
        for control in controls:
            got = read(lookup, workload, seed, devices, peaks, control,
                       args.pool_index)
            got.update(seed=seed, control=control, set=args.sets,
                       pool_index=args.pool_index)
            print(json.dumps(got), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
