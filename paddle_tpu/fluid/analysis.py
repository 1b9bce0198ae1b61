"""Roofline analysis over the Program IR.

Computes, per op, the arithmetic work (FLOPs) and the memory traffic
(bytes moved) implied by the VarDesc shapes, and the resulting time
floor on a machine with a given MXU peak and HBM bandwidth:

    t_op >= max(flops / peak_flops, bytes / bandwidth)

This is the tool behind the ceiling analysis in PERF.md: the device
profile (benchmark/run.py --trace 1) says where the time WENT; this
says where it HAS to go, so the gap between the two is the actionable
headroom.  The reference has no counterpart
(its benchmark suite only reports throughput); on TPU the
compute/bandwidth split is the whole performance story, so the
analyzer is a first-class framework facility.

This module is the COST half of program analysis.  The CORRECTNESS
half — IR verification, alias/race detection, TPU lints over the same
ProgramDescs — is `paddle_tpu.analysis` (docs/ANALYSIS.md).

Model caveats (documented, deliberate):
  * bytes are per-op (every input read + output written once).  XLA
    fuses elementwise chains, so the true traffic sits between the
    per-op sum and the unique-bytes bound where each distinct tensor
    moves through HBM exactly once; both are reported.
  * with ``bf16_act`` (the FLAGS_amp_bf16_act policy), non-persistable
    float tensors count 2 bytes/element; persistable (master weights,
    running stats) stay 4.
  * grad ops for the MXU families count 2x the forward FLOPs (dgrad +
    wgrad are each a same-sized contraction).
"""

from collections import defaultdict

import numpy as np

from ..core.types import GRAD_SUFFIX
from ..ops import registry as op_registry

__all__ = ["program_costs", "roofline_report", "format_report",
           "DEVICE_PEAKS"]

# Published peaks of one chip, keyed by jax's `device_kind` (Google
# Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, 819 GB/s HBM).  The
# one table: a kind that is not here has no peak, and whoever measures
# on it reports no utilization instead of assuming one.
DEVICE_PEAKS = {
    "TPU v5 lite": {"bf16_tflops": 197.0, "hbm_gbps": 819.0},
}

# the static reports price a program for the v5e unless told otherwise
DEFAULT_PEAK_TFLOPS = DEVICE_PEAKS["TPU v5 lite"]["bf16_tflops"]
DEFAULT_HBM_GBPS = DEVICE_PEAKS["TPU v5 lite"]["hbm_gbps"]

_MXU_FWD = {"conv2d", "conv3d", "conv2d_transpose", "conv3d_transpose",
            "depthwise_conv2d", "mul", "matmul"}


def _numel(shape):
    if shape is None:
        return 0
    n = 1
    for s in shape:
        n *= max(int(s), 1)  # -1 (dynamic) counted as 1: caller feeds
    return n                 # static-shape programs for real numbers


def _var_meta(block, name):
    if not name or name.startswith("@"):
        return None
    if not block.has_var_recursive(name):
        return None
    v = block.var_recursive(name)
    return getattr(v, "shape", None), str(getattr(v, "dtype", "float32"))


def _elem_bytes(dtype, persistable, bf16_act):
    size = {"float64": 8, "int64": 8, "float32": 4, "int32": 4,
            "float16": 2, "bfloat16": 2, "uint8": 1, "int8": 1,
            "bool": 1}.get(dtype, 4)
    if bf16_act and size == 4 and dtype.startswith("float") \
            and not persistable:
        return 2
    return size


def _conv_flops(block, od, fwd_type):
    """2 * out_spatial * N * K * C/g * prod(kernel). Output shape is
    the forward Output's; for grad ops it's the O@Output operand."""
    w_slot = "Filter"
    out_name = (od.output("Output") or [None])[0] \
        if od.type == fwd_type else (od.input("O@Output") or [None])[0]
    w_name = (od.input(w_slot) or [None])[0]
    out = _var_meta(block, out_name)
    w = _var_meta(block, w_name)
    if not out or not w or out[0] is None or w[0] is None:
        return 0
    groups = int(od.attr("groups", 1) or 1)
    n_out = _numel(out[0])
    # filter shape [K, C/g, *kernel] (transpose convs store [C, K/g, *])
    per_out = 2 * _numel(w[0]) // max(int(w[0][0]), 1)
    return n_out * per_out // max(groups, 1) * \
        (1 if od.type == fwd_type else 2)


def _mul_flops(block, od, fwd_type):
    out_slot = "Out"
    out_name = (od.output(out_slot) or [None])[0] \
        if od.type == fwd_type else (od.input("O@" + out_slot) or [None])[0]
    x = _var_meta(block, (od.input("X") or [None])[0])
    y = _var_meta(block, (od.input("Y") or [None])[0])
    out = _var_meta(block, out_name)
    if not x or not y or not out or None in (x[0], y[0], out[0]):
        return 0
    k = _numel(y[0]) // max(int(y[0][-1]), 1)  # contracted extent
    flops = 2 * _numel(out[0]) * k
    return flops * (1 if od.type == fwd_type else 2)


def op_cost(block, od, bf16_act=False):
    """(flops, bytes, klass) for one OpDesc."""
    fwd = od.type
    if op_registry.is_grad_op_type(od.type):
        fwd = op_registry.forward_type_of_grad(od.type)
    flops = 0
    if fwd in _MXU_FWD:
        if fwd.startswith("conv") or fwd == "depthwise_conv2d":
            flops = _conv_flops(block, od, fwd)
        else:
            flops = _mul_flops(block, od, fwd)
        klass = "mxu"
    else:
        klass = "hbm"
    total_bytes = 0
    for names in list(od.inputs.values()) + list(od.outputs.values()):
        for n in names:
            total_bytes += _tensor_bytes(block, n, bf16_act)
    return flops, total_bytes, klass


def _tensor_bytes(block, name, bf16_act):
    meta = _var_meta(block, name)
    if not meta or meta[0] is None:
        return 0
    v = block.var_recursive(name)
    esize = _elem_bytes(meta[1], bool(getattr(v, "persistable", False)),
                        bf16_act)
    return _numel(meta[0]) * esize


def program_costs(program, bf16_act=False, block=None):
    """Per-op cost rows for the global block (or ``block``):
    [(op_type, flops, bytes, klass), ...] in op order."""
    block = block if block is not None else program.global_block()
    return [(od.type,) + op_cost(block, od, bf16_act)
            for od in block.desc.ops]


def _unique_bytes(block, bf16_act):
    """Bytes if every referenced tensor moved exactly once — the
    perfect-fusion traffic floor (intermediates inside a fusion are
    free, but each distinct value is produced/consumed through HBM at
    least once)."""
    seen = set()
    total = 0
    for od in block.desc.ops:
        for names in list(od.inputs.values()) + list(od.outputs.values()):
            for n in names:
                if n not in seen:
                    seen.add(n)
                    total += _tensor_bytes(block, n, bf16_act)
    return total


def roofline_report(program, peak_tflops=DEFAULT_PEAK_TFLOPS,
                    hbm_gbps=DEFAULT_HBM_GBPS, bf16_act=False,
                    block=None):
    """Aggregate time floors.  Returns a dict with per-op-type rows and
    two step floors:
      * ``floor_ms_serial`` — sum over ops of max(t_mxu, t_hbm): every
        op runs alone, no fusion (pessimistic traffic, realistic
        serialization);
      * ``floor_ms_ideal`` — max(total FLOPs / peak, unique bytes /
        bw): perfect fusion (each distinct tensor moves once) and
        perfect compute/memory overlap.
    The measured step time should land between them; distance from
    ``floor_ms_serial`` is fusion/overlap win, distance of
    ``floor_ms_serial`` from ``floor_ms_ideal`` is the remaining
    fusion headroom."""
    block_ = block if block is not None else program.global_block()
    rows = program_costs(program, bf16_act=bf16_act, block=block_)
    peak = peak_tflops * 1e12
    bw = hbm_gbps * 1e9
    agg = defaultdict(lambda: [0, 0, 0, 0.0])  # count, flops, bytes, t
    t_serial = 0.0
    tot_flops = 0
    tot_bytes = 0
    for op_type, flops, nbytes, _ in rows:
        t = max(flops / peak, nbytes / bw)
        a = agg[op_type]
        a[0] += 1
        a[1] += flops
        a[2] += nbytes
        a[3] += t
        t_serial += t
        tot_flops += flops
        tot_bytes += nbytes
    uniq = _unique_bytes(block_, bf16_act)
    return {
        "per_type": {k: {"count": v[0], "gflops": v[1] / 1e9,
                         "mbytes": v[2] / 1e6, "t_ms": v[3] * 1e3}
                     for k, v in agg.items()},
        "total_gflops": tot_flops / 1e9,
        "total_gbytes": tot_bytes / 1e9,
        "unique_gbytes": uniq / 1e9,
        "floor_ms_serial": t_serial * 1e3,
        "floor_ms_ideal": max(tot_flops / peak, uniq / bw) * 1e3,
        "peak_tflops": peak_tflops,
        "hbm_gbps": hbm_gbps,
        "bf16_act": bf16_act,
    }


def format_report(report, topk=12):
    lines = ["%-28s %6s %12s %12s %10s" % (
        "op type", "count", "GFLOP", "MB moved", "t floor ms")]
    per = sorted(report["per_type"].items(),
                 key=lambda kv: -kv[1]["t_ms"])
    for k, v in per[:topk]:
        lines.append("%-28s %6d %12.2f %12.1f %10.3f" % (
            k, v["count"], v["gflops"], v["mbytes"], v["t_ms"]))
    if len(per) > topk:
        rest = per[topk:]
        lines.append("%-28s %6d %12.2f %12.1f %10.3f" % (
            "(%d more types)" % len(rest),
            sum(v["count"] for _, v in rest),
            sum(v["gflops"] for _, v in rest),
            sum(v["mbytes"] for _, v in rest),
            sum(v["t_ms"] for _, v in rest)))
    lines.append("")
    lines.append("total %.1f GFLOP, %.2f GB per-op / %.2f GB unique  "
                 "(peak %.0f TFLOP/s, %.0f GB/s, bf16_act=%s)"
                 % (report["total_gflops"], report["total_gbytes"],
                    report["unique_gbytes"], report["peak_tflops"],
                    report["hbm_gbps"], report["bf16_act"]))
    lines.append("step floor: %.2f ms serial-per-op  |  %.2f ms "
                 "perfectly-fused" % (report["floor_ms_serial"],
                                      report["floor_ms_ideal"]))
    return "\n".join(lines)
