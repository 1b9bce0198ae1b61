"""The controls of the long-session cell's `correct`, at the cell's own
size on the chip or at a toy size under pytest (test_exaone_cell.py).

    python3 benchmark/tests/long_control.py \
        --workload exaone-turn-32k-ep16 --seeds 11,12 \
        [--lower serve_dtype=float8_e4m3fn] [--lower window=64] \
        [--ring-off] [--drop-last] [--set weights.qk_gain=4] \
        [--search-path DIR]

benchmark/tests/session_control.py's loop (for every seed, in one
process: set-up makes the session, the system serves one call of the
cell from it, and the plain reference then reads, over the checked rows
of that call and the probes of its last step, the numbers `correct`
compares; then the same for every control).  A `--lower` is the
program's own path with that one key of the workload changed, held to
the reference of the cell as stated: keys and values kept in float8 (the
session handed in rounds to it too), a window of 64 slots in place of
128.  `--ring-off` and `--drop-last` are two controls more, faults put
into the program's ops: a window layer's ring written one slot off (the
ring wraps a slot early: the step writes slot Position mod (window - 1),
so that the ring's last slot is never written again and the step attends
the window - 1 most recent positions and one that left the window long
ago.  A *constant* offset, slot (Position + 1) mod window, is no fault
once the ring has wrapped: it names the slots otherwise and the ring
holds the same positions, which is what this control first was and read
0 in every window layer), and
a token's last held expert dropped (of the experts a token chose that
this chip holds, the last one's weight is 0).  The limits in the
workload file lie between the sound line and the controls' lines this
prints; the benchmark's own runs never run it.
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

RING_OFF = "a window layer's ring written one slot off"
DROP_LAST = "a token's last held expert dropped"


@contextlib.contextmanager
def _kernel(op_type, wrap):
    from paddle_tpu.ops import registry

    info = registry.get_op_info(op_type)
    real = info.kernel
    info.kernel = lambda ctx, ins, attrs: wrap(real, ctx, ins, attrs)
    try:
        yield
    finally:
        info.kernel = real


def ring_written_one_slot_off():
    """`cached_attention` with a `window` writes slot Position mod
    (window - 1) and attends the whole ring (the op is handed a position
    that says so: that slot's, a lap on); a full layer's op is as it
    was."""
    def off(real, ctx, ins, attrs):
        window = attrs.get("window", 0)
        if window:
            ins = dict(ins, Position=[
                ins["Position"][0] % (window - 1) + window])
        return real(ctx, ins, attrs)

    return _kernel("cached_attention", off)


def last_held_expert_dropped():
    """`moe_experts` with the weight of the last of a token's chosen
    experts that the op holds set to 0."""
    import jax.numpy as jnp
    from paddle_tpu.ops.moe import _held_range

    def dropped(real, ctx, ins, attrs):
        top_w, top_idx = ins["TopW"][0], ins["TopIdx"][0]
        experts = ins["WGate"][0].shape[0]
        first, _ = _held_range(attrs, experts)
        held = (top_idx >= first) & (top_idx < first + experts)
        at = jnp.arange(top_idx.shape[1])
        last = jnp.max(jnp.where(held, at, -1), axis=1, keepdims=True)
        return real(ctx, dict(ins, TopW=[jnp.where(at == last, 0, top_w)]),
                    attrs)

    return _kernel("moe_experts", dropped)


FAULTS = {RING_OFF: ring_written_one_slot_off,
          DROP_LAST: last_held_expert_dropped}


def read(lookup, workload, seed, devices, peaks, control=None):
    """What `correct` compares (decode_long.compare's numbers, and
    "memory_peak_bytes" while serving) of one call of the cell `workload`
    at `seed`, served under `control` (a `--lower` assignment, a fault or
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
    lowered = control is not None and control not in FAULTS
    served = a_run(decode_control.changed(workload, control) if lowered
                   else workload)
    init, inputs = driver.make_session(served, model, documents)
    with FAULTS[control]() if control in FAULTS \
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
    p.add_argument("--ring-off", action="store_true")
    p.add_argument("--drop-last", action="store_true")
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
    controls = ([] if args.no_sound else [None]) + args.lower \
        + ([RING_OFF] if args.ring_off else []) \
        + ([DROP_LAST] if args.drop_last else [])
    for seed in (int(s) for s in args.seeds.split(",")):
        for control in controls:
            got = read(lookup, workload, seed, devices, peaks, control)
            got.update(seed=seed, control=control, set=args.sets)
            print(json.dumps(got), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
