"""The controls of the reuse cell's `correct`, at the cell's own size on
the chip or at a toy size under pytest (test_hy4_cell.py).

    python3 benchmark/tests/reuse_control.py \
        --workload hy4-turn-32k-ep16 --seeds 11,12 \
        [--lower serve_dtype=float8_e4m3fn] \
        [--lower index_dtype=float8_e4m3fn] \
        [--lower control.gated=false] [--lower control.sink=false] \
        [--lower control.hc_iterations=1] [--recent] \
        [--set weights.sink_mean=6] [--search-path DIR]

benchmark/tests/session_control.py's loop (for every seed, in one
process: set-up makes the session, the system serves one call of the cell
from it, and the plain reference then reads, over the checked rows of
that call and the probes of its last step, the numbers `correct`
compares; then the same for every control), with the reference's session
made once a seed and rounded again for each control.  A `--lower` is the
program's own path with that one key of the workload changed, held to the
reference of the cell as stated: a float8 latent cache (the session
handed in rounds to it too), index keys cached in float8, the step built
without the attention's output gate, without the sink, or with one
Sinkhorn iteration in place of twenty (`control.*`: decode_reuse.py
`build`).  `--recent` is one control more: every layer that inherits its
set attends the most recent `top_k` slots in place of it, what such a
layer would do if the inherited set never reached it and a window stood
in.  The limits in the workload file lie between the sound line and the
controls' lines this prints; the benchmark's own runs never run it.
"""

import argparse
import contextlib
import copy
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(os.path.dirname(HERE)))

from benchmark import harness  # noqa: E402
from benchmark.tests import decode_control  # noqa: E402

RECENT = "the most recent slots in place of the inherited set"


def changed(workload, assignment):
    """decode_control's `changed`; a `control.*` key makes its group."""
    if assignment.startswith("control."):
        workload = dict(copy.deepcopy(workload),
                        control=dict(workload.get("control", {})))
    return decode_control.changed(workload, assignment)


@contextlib.contextmanager
def inheriting_layers_attend_recent_slots(kinds):
    """`mla_cached_attention` with `Selected` = Position, Position - 1,
    ... on the layers `kinds` calls "shared", whatever they were handed
    (a slot below 0 names the last one, which is past Position and masked
    by `Live` as any dead entry is).  The kernel is lowered once a layer
    and trace, in the layers' order."""
    import jax.numpy as jnp
    from paddle_tpu.ops import registry

    info = registry.get_op_info("mla_cached_attention")
    real, calls = info.kernel, [0]

    def recent(ctx, ins, attrs):
        layer = calls[0] % len(kinds)
        calls[0] += 1
        if kinds[layer] == "shared":
            pos = jnp.reshape(ins["Position"][0], (-1,))[0].astype(jnp.int32)
            chosen = ins["Selected"][0]
            slots = pos - jnp.arange(chosen.shape[1], dtype=jnp.int32)
            slots = jnp.where(slots >= 0, slots,
                              ins["Cache"][0].shape[1] - 1)
            ins = dict(ins, Selected=[jnp.broadcast_to(slots, chosen.shape)])
        return real(ctx, ins, attrs)

    info.kernel = recent
    try:
        yield
    finally:
        info.kernel = real


def reader(lookup, workload, seed, devices, peaks):
    """`read(control)` for one seed: what `correct` compares
    (decode_reuse.compare's numbers, and "memory_peak_bytes" while
    serving) of one call of the cell `workload`, served under `control`
    (a `--lower` assignment, RECENT or None) and compared as the cell
    states.  The reference's session is made at the first call."""
    config = lookup.json("configs", workload["config"])

    def a_run(cell):
        return harness.Run(cell, config, seed, 0.0, False, lookup, devices,
                           peaks, harness.SetupClock(time.perf_counter()),
                           harness.CompileClock())

    driver = lookup.module("drivers", workload["driver"])
    model = lookup.module("models", workload["builder"])
    pool = model.prompts(config, workload, seed)
    documents = model.documents(config, workload, seed)
    made = [None]

    def read(control=None):
        lowered = control not in (None, RECENT)
        served = a_run(changed(workload, control) if lowered else workload)
        init, made[0] = driver.make_session(served, model, documents,
                                            made[0])
        with inheriting_layers_attend_recent_slots(
                model.layer_kinds(config)[0]) if control == RECENT \
                else contextlib.nullcontext():
            generate = driver.serve(served, model, init,
                                    driver.build(served, model))
            del init
            call = (0,) + generate(pool[0], workload["gen_len"])
        peak = harness.memory_peak_bytes(devices)
        del generate
        got = driver.compare(a_run(workload), model, documents, pool, call,
                             made[0])
        got["memory_peak_bytes"] = peak     # a sizing trial reads it
        return got

    return read


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
        workload = changed(workload, assignment)
    devices, peaks = harness.require_devices(workload["chips"], lookup)
    harness.place_compile_cache()
    controls = [None] + args.lower + ([RECENT] if args.recent else [])
    for seed in (int(s) for s in args.seeds.split(",")):
        read = reader(lookup, workload, seed, devices, peaks)
        for control in controls:
            got = read(control)
            got.update(seed=seed, control=control, set=args.sets)
            print(json.dumps(got), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
