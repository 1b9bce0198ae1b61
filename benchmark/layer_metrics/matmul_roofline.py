"""The matrix products' share of their roofline: the time the FLOPs of
the program's `mul` and `matmul` ops and of their gradients
(benchmark/flops/instances.py, the gradients really produced) take at
the bf16 peak, over the device time under those ops' instances
(benchmark/reduce/op_instances.py).  `mxu_roofline` cannot say this
since the attention backward became kernels: it counts their FLOPs and
not their time.  The products are compute-bound at the cells' shapes.

Prints the instances grouped by shape (rows x contraction x columns):
how many, forward and backward ms a step, their share.  A product with
no operation under its instance (its fusion rooted at a neighbour) is
left out on both sides and said.  A program whose configuration has no
vocabulary (no language model) gets no value: its one product is the
classifier.  First device, traced window."""

import collections

from benchmark.flops import instances
from benchmark.reduce import op_instances

LAYER = "kernels"
MOVES = "train_items_per_s"
UNIT = "%"
SOURCE = "device_trace"
VOCABULARY = "vocab_size"


def rows(run):
    """One row a product of the program that has an operation under its
    instance, seconds a step: {"instance", "shape": (rows, contraction,
    columns), "forward", "backward", "floor"}; None where nothing is to
    be read."""
    found = op_instances.seconds(run)
    steps = run.facts.get("traced_steps")
    if not found or not steps or run.peaks is None \
            or VOCABULARY not in run.config:
        return None
    peak = run.peaks["bf16_flops_per_s"] * run.facts["chips"]
    out = []
    for (base, inst), e in (instances.of_run(run) or {}).items():
        if e["kind"] != instances.MATMUL or not (
                (base, inst) in found or (base + "_grad", inst) in found):
            continue
        columns = e["output"][-1]
        total = 1
        for d in e["output"]:
            total *= d
        out.append({
            "instance": inst,
            "shape": (total // columns, e["forward"] // (2 * total), columns),
            "forward": found.get((base, inst), [0.0])[0] / steps,
            "backward": found.get((base + "_grad", inst), [0.0])[0] / steps,
            "floor": (e["forward"] + e["backward"]) / peak})
    return out or None


def read(run):
    found = rows(run)
    if not found:
        return None
    groups = collections.defaultdict(lambda: [0, 0.0, 0.0, 0.0])
    for r in found:
        g = groups[r["shape"]]
        g[0] += 1
        g[1] += r["forward"]
        g[2] += r["backward"]
        g[3] += r["floor"]
    took = sum(r["forward"] + r["backward"] for r in found)
    program = sum(1 for e in instances.of_run(run).values()
                  if e["kind"] == instances.MATMUL)
    if len(found) < program:
        print("%d products of the program have no operation under their "
              "instance and are left out" % (program - len(found)),
              flush=True)
    print("matrix products: %d, %.3f ms a step under them, %.3f at the "
          "bf16 peak; by shape (rows x contraction x columns: products, "
          "forward / backward ms, share of the roofline): %s"
          % (len(found), took * 1e3, sum(r["floor"] for r in found) * 1e3,
             "; ".join(
                 "%dx%dx%d: %d, %.3f / %.3f, %.1f%%"
                 % (shape + (n, fwd * 1e3, bwd * 1e3,
                             100.0 * floor / (fwd + bwd) if fwd + bwd else 0))
                 for shape, (n, fwd, bwd, floor) in sorted(
                     groups.items(), key=lambda item: -item[1][1]
                     - item[1][2]))), flush=True)
    return 100.0 * sum(r["floor"] for r in found) / took
