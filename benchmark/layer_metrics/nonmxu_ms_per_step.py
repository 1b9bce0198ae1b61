"""Device milliseconds a step spends in every other operation: batch
norm, elementwise, reductions, copies, the optimizer's update, custom
calls, collectives."""

LAYER = "ops"
MOVES = "train_items_per_s"
UNIT = "ms"
SOURCE = "device_trace"


def read(run):
    found = run.lookup.module("layer_metrics", "mxu_ms_per_step").seconds(run)
    if found is None:
        return None
    return (found[1] - found[0]) / run.facts["traced_steps"] * 1e3
