"""Device milliseconds a step spends inside all-reduce, all-gather,
reduce-scatter, collective-permute and all-to-all operations on the
first device.  Absent where the trace has none (one chip)."""

from benchmark.reduce import xplane

LAYER = "multichip"
MOVES = "train_items_per_s"
UNIT = "ms"
SOURCE = "device_trace"


def seconds(run):
    trace = run.reduced
    if trace is None or not trace.devices or "traced_steps" not in run.facts:
        return None
    device = trace.devices[min(trace.devices)]
    total, exposed = xplane.collective_seconds(device, trace.window)
    return (total, exposed) if total else None


def read(run):
    found = seconds(run)
    if found is None:
        return None
    return found[0] / run.facts["traced_steps"] * 1e3
