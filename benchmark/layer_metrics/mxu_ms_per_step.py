"""Device milliseconds a step spends in convolutions and matrix products,
each with whatever XLA fused around it: the fusions of kind kOutput and
any unfused `convolution` or `dot` (benchmark/reduce/xplane.py says why
that is the rule).  First device, traced window, over its steps."""

from benchmark.reduce import xplane

LAYER = "ops"
MOVES = "train_items_per_s"
UNIT = "ms"
SOURCE = "device_trace"


def seconds(run):
    trace = run.reduced
    if trace is None or not trace.devices or "traced_steps" not in run.facts:
        return None
    device = trace.devices[min(trace.devices)]
    by_category = xplane.category_seconds(device, trace.window)
    return sum(s for c, s in by_category.items()
               if c in xplane.MXU_CATEGORIES), \
        sum(by_category.values())


def read(run):
    found = seconds(run)
    if found is None:
        return None
    return found[0] / run.facts["traced_steps"] * 1e3
