"""Device milliseconds a step that the five convolutions furthest from
their FLOP floor spend above it: for each `conv2d` op of the program, the
device time under its instance, forward and backward joined
(benchmark/reduce/op_instances.py), less the time its FLOPs
(benchmark/flops/instances.py, the gradients really produced) take at
the bf16 peak; the five largest, summed.  Against the whole distance
between the convolutions and their floor this says whether a kernel for
a few shapes or a question for all of them is what ROADMAP Speed 1 buys.

Prints every convolution sorted by it: instance, filter, output, stride,
forward / input-gradient / weight-gradient ms a step, the share of its
FLOP roofline, and the time the bytes its operations move
(benchmark/flops/elementwise.py `instruction_bytes`) take at the HBM
peak: a convolution kept from its FLOP floor by those is memory-bound as
XLA fused it, and no faster MXU loop helps it.  The two gradients are
told apart by the shape an operation writes; what writes both or neither
(a fusion of both, a cast of the filter) is printed apart as `other`.
First device, traced window, over its steps; a program without
convolutions or without instance scopes gets no value."""

import functools

from benchmark.flops import instances
from benchmark.reduce import op_instances

LAYER = "ops"
MOVES = "train_items_per_s"
UNIT = "ms"
SOURCE = "device_trace"
WORST = 5


@functools.lru_cache(maxsize=1)
def table(run):
    """One row a convolution that has an operation under its instance,
    the furthest from its floor first, times in seconds a step:
    {"instance", "filter", "output", "strides", "forward", "input",
    "weight", "other", "backward" (the three before it), "floor_forward",
    "floor_backward", "at_hbm_peak", "lost"}; None where there is nothing
    to read.  (Kept: three readers share it.)"""
    found = op_instances.seconds(run)
    steps = run.facts.get("traced_steps")
    if not found or not steps or run.peaks is None:
        return None
    convs = {key: e for key, e in (instances.of_run(run) or {}).items()
             if e["kind"] == instances.CONV}
    peak = run.peaks["bf16_flops_per_s"] * run.facts["chips"]
    hbm = run.peaks["hbm_bytes_per_s"]
    rows = []
    for base in sorted({base for base, _ in convs}):
        grad = base + "_grad"
        shapes = {inst: (e["filter"], e["input"])
                  for (b, inst), e in convs.items() if b == base}
        by_kind = op_instances.conv_grad_seconds(run, shapes, grad)
        for inst in shapes:
            entry, kinds = convs[base, inst], by_kind.get(inst, {})
            row = {"instance": inst, "filter": entry["filter"],
                   "output": entry["output"], "strides": entry["strides"],
                   "forward": found.get((base, inst), [0.0])[0] / steps,
                   "input": kinds.get(op_instances.INPUT, 0.0) / steps,
                   "weight": kinds.get(op_instances.WEIGHT, 0.0) / steps,
                   "other": (kinds.get(op_instances.BOTH, 0.0)
                             + kinds.get(op_instances.OTHER, 0.0)) / steps,
                   "floor_forward": entry["forward"] / peak,
                   "floor_backward": entry["backward"] / peak,
                   "at_hbm_peak": sum(
                       found[kind, inst][4] for kind in (base, grad)
                       if (kind, inst) in found) / steps / hbm}
            row["backward"] = row["input"] + row["weight"] + row["other"]
            row["lost"] = row["forward"] + row["backward"] \
                - row["floor_forward"] - row["floor_backward"]
            # a convolution with no operation under either scope (its
            # fusions rooted at a neighbour) has no time to set against
            # its floor
            if row["forward"] or row["backward"]:
                rows.append(row)
    return sorted(rows, key=lambda row: -row["lost"]) or None


def read(run):
    rows = table(run)
    if not rows:
        return None
    spent = sum(r["forward"] + r["backward"] for r in rows)
    lost = sum(r["lost"] for r in rows)
    bound = [r for r in rows
             if r["at_hbm_peak"] > r["floor_forward"] + r["floor_backward"]]
    print("convolutions: %d with operations under them, %.3f ms a step, "
          "%.3f at the bf16 peak, %.3f lost; %.3f of that in the %d whose "
          "operations move bytes that take longer at the HBM peak (%.3f ms) "
          "than their FLOPs at the bf16 peak.  By instance (filter -> "
          "output /stride: forward / input-gradient / weight-gradient / "
          "other ms, share of the FLOP roofline, ms lost, ms of its bytes "
          "at the HBM peak):"
          % (len(rows), spent * 1e3, (spent - lost) * 1e3, lost * 1e3,
             sum(r["lost"] for r in bound) * 1e3, len(bound),
             sum(r["at_hbm_peak"] for r in bound) * 1e3), flush=True)
    for r in rows:
        took = r["forward"] + r["backward"]
        print("  %s %s -> %s /%s: %.3f / %.3f / %.3f / %.3f, %.1f%%, %.3f, "
              "%.3f" % (r["instance"], "x".join(map(str, r["filter"])),
                        "x".join(map(str, r["output"])),
                        "x".join(map(str, r["strides"])),
                        r["forward"] * 1e3, r["input"] * 1e3,
                        r["weight"] * 1e3, r["other"] * 1e3,
                        100.0 * (took - r["lost"]) / took,
                        r["lost"] * 1e3, r["at_hbm_peak"] * 1e3), flush=True)
    return sum(r["lost"] for r in rows[:WORST]) * 1e3
