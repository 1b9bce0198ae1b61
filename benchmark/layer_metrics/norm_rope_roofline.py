"""The `rms_norm` and `rope` ops' share of the HBM peak, as they ran: the
bytes the instructions under those ops' scopes and their gradients' move
(benchmark/flops/elementwise.py `instruction_bytes`, from each
instruction's own results and operands in the trace) over the HBM peak,
against the device time of the same instructions.  These ops are
memory-bound, a few FLOPs a byte, so the bound is the bytes one.

What XLA fused into a neighbour (a norm into the product that reads it)
is no instruction under these scopes and counts on neither side.  How
much that is, the printed line says: the bytes the IR's ops would move
as kernels of their own (`program_bytes`; the program is built once more
for its shapes, the driver does not keep it) beside the bytes that were
moved.  First device, the instructions wholly inside the traced
window."""

from benchmark.flops import elementwise
from benchmark.reduce import op_scopes, xplane

LAYER = "kernels"
MOVES = "train_items_per_s"
UNIT = "%"
SOURCE = "device_trace"


def moved(run, op_types):
    """(bytes, seconds, instructions) of the instructions under the
    scopes of `op_types` that lie wholly inside the traced window, or
    None where the trace names no op."""
    scoped = op_scopes.of_run(run)
    if scoped is None:
        return None
    ordinal = min(run.reduced.devices)
    paths = op_scopes.metadata_stat(xplane.find_xplane(run.trace_dir),
                                    "/device:TPU:%d" % ordinal, "tf_op")
    texts = {xplane.parse_instruction(text)[0]: text
             for text, path in paths.items()
             if op_scopes.op_type(path) in op_types}
    first, last = scoped.window
    nbytes, seconds, count = 0, 0.0, 0
    for start, end, name, path in scoped.ops:
        if op_scopes.op_type(path) in op_types and name in texts \
                and first <= start and end <= last:
            nbytes += elementwise.instruction_bytes(texts[name])
            seconds += end - start
            count += 1
    return nbytes, seconds, count


def read(run):
    import jax.numpy as jnp

    times = run.lookup.module("layer_metrics", "norm_rope_ms_per_step")
    if run.peaks is None or times.seconds(run) is None:
        return None
    nbytes, seconds, count = moved(run, times.OP_TYPES)
    if not seconds:
        return None
    cfg, steps = run.config, run.facts["traced_steps"]
    program = run.lookup.module("models", cfg["builder"]).build(
        cfg, run.workload["batch"], train=True)["main"]
    alone = elementwise.program_bytes(
        program, jnp.dtype(cfg["compute_dtype"]).itemsize)
    peak = run.peaks["hbm_bytes_per_s"]
    print("norm and rope: %.1f instructions a step moved %.3f GB in %.3f "
          "ms (%.3f ms at the HBM peak); as kernels of their own the "
          "program's %d ops would move %.3f GB (%.3f ms at the peak): "
          "the rest is fused into neighbours"
          % (count / steps, nbytes / steps / 1e9, seconds / steps * 1e3,
             nbytes / steps / peak * 1e3,
             sum(e["calls"] for e in alone["ops"].values()),
             alone["total"] / 1e9, alone["total"] / peak * 1e3), flush=True)
    return 100.0 * nbytes / peak / seconds
