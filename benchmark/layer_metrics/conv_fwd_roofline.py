"""The forward convolutions' share of their roofline: the time the FLOPs
of the program's `conv2d` ops (benchmark/flops/instances.py) take at the
bf16 peak, over the device time under those ops' instances
(benchmark/reduce/op_instances.py), which holds whatever XLA fused
around each convolution.  Convolutions are compute-bound at the cell's
shapes, so the bound is the FLOP one.  The per-convolution table is
`conv_top5_lost_ms_per_step`'s; a convolution with no operation under
its scope (its fusion rooted at a neighbour) is left out on both sides
and said."""

from benchmark.flops import instances

LAYER = "kernels"
MOVES = "train_items_per_s"
UNIT = "%"
SOURCE = "device_trace"
WHICH = "forward"


def share(run, which):
    rows = run.lookup.module(
        "layer_metrics", "conv_top5_lost_ms_per_step").table(run)
    if not rows:
        return None
    seen = [r for r in rows if r[which]]
    program = sum(1 for e in instances.of_run(run).values()
                  if e["kind"] == instances.CONV)
    if len(seen) < program:
        print("%d %s convolutions of the program have no operation under "
              "their instance and are left out" % (program - len(seen),
                                                   which), flush=True)
    took = sum(r[which] for r in seen)
    return 100.0 * sum(r["floor_" + which] for r in seen) / took \
        if took else None


def read(run):
    return share(run, WHICH)
