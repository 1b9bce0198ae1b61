"""The share of the HBM roofline a decode step reaches on the device: the
bytes one step must move (benchmark/flops/decode.py: every weight once
and the *live* part of the key/value cache, averaged over the call's
decode steps, in the types they are served in) at the chip's published
HBM peak, over the device's time a decode step: the seconds an operation
ran inside the traced call's decoding scan (the second of the call's two
`while` operations on the first device, benchmark/reduce/scans.py), over
its `gen_len - 1` steps.  Nothing of the host, of the prefill or of
another program is in it (`decode_step_ms` is the host clock's reading
of a step, beside it).  Memory bounds a step: its 0.8 GFLOP a row are
microseconds of the MXU.

Prints beside it the prefill scan's device time a step, what a step
that reads the whole extent of the cache would have to move, and the
bytes the traced call's operations state: every operation of the device
trace at the operands and results of its HLO instruction
(benchmark/flops/elementwise.py `instruction_bytes`), over the call's
step applications, prefill's among them.  That count is of arrays as
declared, not as laid out (a 64-wide minor axis is padded to 128 lanes
in memory), and an update in place (`dynamic-update-slice`) states its
whole operand twice though it writes one slot, so those are given
apart."""

from benchmark.flops import decode, elementwise
from benchmark.reduce import op_instances, scans, xplane

LAYER = "kernels"
MOVES = "decode_tok_per_s"
UNIT = "%"
SOURCE = "device_trace"


IN_PLACE = "dynamic-update-slice"


def traced_bytes(run):
    """(bytes the first device's operations in the traced window state
    but for the updates in place, bytes those state), or None."""
    trace = run.reduced
    if trace is None or not trace.devices:
        return None
    text_of = op_instances.texts(run)
    moved = {}
    totals = [0, 0]
    for op in trace.devices[min(trace.devices)].work:
        if not xplane.clip([(op.start, op.end)], *trace.window):
            continue
        if op.name not in moved:
            moved[op.name] = elementwise.instruction_bytes(
                text_of.get(op.name, ""))
        totals[IN_PLACE in op.name] += moved[op.name]
    return tuple(totals)


def scan_step_seconds(run):
    """Device seconds a step of the traced call's (prefill scan, decoding
    scan), or None where the trace does not hold the two."""
    trace, facts = run.reduced, run.facts
    if trace is None or not trace.devices or "gen_len" not in facts:
        return None
    device = trace.devices[min(trace.devices)]
    found = scans.outermost(device, trace.window)
    if len(found) != 2 or facts["gen_len"] < 2:
        return None
    prefill, decoding = (scans.busy_seconds(device, span) for span in found)
    return (prefill / (facts["prompt_len"] - 1),
            decoding / (facts["gen_len"] - 1))


def read(run):
    facts, peaks = run.facts, run.peaks
    steps = scan_step_seconds(run) if peaks is not None else None
    if steps is None:
        return None
    import jax.numpy as jnp

    cfg, workload = run.config, run.workload
    weights = jnp.dtype(workload["weights"]["dtype"]).itemsize
    cache = jnp.dtype(workload["serve_dtype"]).itemsize
    prompt, gen = facts["prompt_len"], facts["gen_len"]
    # the decode steps write slots prompt .. prompt + gen - 2
    must = decode.mean_step_bytes(cfg, facts["batch"], prompt,
                                  prompt + gen - 2, weights, cache)
    whole = decode.whole_extent_step_bytes(cfg, facts["batch"], weights,
                                           cache)
    read_once = decode.weight_bytes(cfg, facts["batch"], weights)
    moved = traced_bytes(run)
    stated = ["%.3f" % (b / facts["traced_step_applications"] / 1e9)
              for b in moved] if moved else ["no", "no"]
    print("decode step: %.4f ms on the device (a prefill step %.4f); must "
          "move %.3f GB (weights %.3f, live cache %.3f), %.3f ms at the "
          "HBM peak; the whole cache extent would be %.3f GB; the traced "
          "call's operations state %s GB a step application and its "
          "updates in place %s"
          % (steps[1] * 1e3, steps[0] * 1e3, must / 1e9, read_once / 1e9,
             (must - read_once) / 1e9,
             must / peaks["hbm_bytes_per_s"] * 1e3, whole / 1e9,
             stated[0], stated[1]), flush=True)
    return 100.0 * must / peaks["hbm_bytes_per_s"] / steps[1]
