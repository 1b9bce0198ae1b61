"""The share of the traced window in which no operation ran on the first
device: 1 - union of its busy intervals over the window."""

from benchmark.reduce import xplane

LAYER = "device"
MOVES = "train_items_per_s"
UNIT = "%"
SOURCE = "device_trace"


def read(run):
    trace = run.reduced
    if trace is None or not trace.devices:
        return None
    start, end = trace.window
    device = trace.devices[min(trace.devices)]
    return 100.0 * (1.0 - xplane.length(xplane.busy(device, trace.window))
                    / (end - start))
