"""The controls of the session cell's `correct`, at the cell's own size
on the chip or at a toy size under pytest (test_dsv32_cell.py).

    python3 benchmark/tests/session_control.py \
        --workload dsv32-turn-16k-ep16 --seeds 11,12 \
        [--lower serve_dtype=float8_e4m3fn] \
        [--lower index_dtype=float8_e4m3fn] [--lower index_topk=1024] \
        [--lower weights.routed_mantissa_bits=3] [--recent] [--set weights.q_gain=4] [--search-path DIR]

benchmark/tests/share_control.py's loop (for every seed, in one process:
set-up makes the session, the system serves one call of the cell from
it, and the plain reference then reads, over the checked rows of that
call and the probes of its last step, the numbers `correct` compares;
then the same for every control).  A `--lower` is the program's own path
with that one key of the workload changed, held to the reference of the
cell as stated: a float8 latent cache (the session handed in rounds to
it too), index keys cached in float8 (three mantissa bits), half as many
slots chosen.  `--recent` is one control more: the chooser's scores are
thrown away and every step attends the most recent `top_k` slots, what a
sliding window would do.  The limits in the workload file lie between
the sound line and the controls' lines this prints; the benchmark's own
runs never run it.
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

RECENT = "the most recent slots in place of the chosen"


@contextlib.contextmanager
def most_recent_slots_chosen():
    """`mla_index_select` with `Selected` = Position, Position - 1, ...
    whatever the scores say (a slot below 0 names the last one, which is
    past Position and masked by `Live` as any dead entry is)."""
    import jax.numpy as jnp
    from paddle_tpu.ops import registry

    info = registry.get_op_info("mla_index_select")
    real = info.kernel

    def recent(ctx, ins, attrs):
        out = real(ctx, ins, attrs)
        pos = jnp.reshape(ins["Position"][0], (-1,))[0].astype(jnp.int32)
        chosen = out["Selected"][0]
        slots = pos - jnp.arange(chosen.shape[1], dtype=jnp.int32)
        slots = jnp.where(slots >= 0, slots, ins["Cache"][0].shape[1] - 1)
        return dict(out, Selected=[jnp.broadcast_to(slots, chosen.shape)])

    info.kernel = recent
    try:
        yield
    finally:
        info.kernel = real


def read(lookup, workload, seed, devices, peaks, control=None):
    """What `correct` compares (decode_session.compare's numbers, and
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
    lowered = control not in (None, RECENT)
    served = a_run(decode_control.changed(workload, control) if lowered
                   else workload)
    init, inputs = driver.make_session(served, model, documents)
    with most_recent_slots_chosen() if control == RECENT \
            else contextlib.nullcontext():
        generate = driver.serve(served, model, init,
                                driver.build(served, model))
        call = (0,) + generate(pool[0], workload["gen_len"])
    peak = harness.memory_peak_bytes(devices)
    del generate, init
    got = driver.compare(a_run(workload), model, documents, pool, call,
                         inputs)
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
