"""The convolution and matrix-product operations' share of their
roofline: the least time one chip's share of the step's static conv and
matmul FLOPs takes at the published bf16 peak, over mxu_ms_per_step.
These operations are compute-bound at the cells' shapes, so the bound is
the FLOP one."""

LAYER = "kernels"
MOVES = "train_items_per_s"
UNIT = "%"
SOURCE = "device_trace"


def read(run):
    facts = run.facts
    found = run.lookup.module("layer_metrics", "mxu_ms_per_step").seconds(run)
    if found is None or run.peaks is None or not found[0]:
        return None
    least = facts["flops"]["mxu"] / facts["chips"] \
        / run.peaks["bf16_flops_per_s"]
    return 100.0 * least / (found[0] / facts["traced_steps"])
