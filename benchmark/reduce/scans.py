"""The scans of a traced generation call on the device.

`jax.lax.scan` reaches the device as one `while` operation, which the
trace records from its first step to its last around the operations of
its body (xplane.py leaves such containers out of every sum).  A
generation call of `fluid.ProgramDecoder` with a prompt is two of them,
one after the other: the prefill's scan over the prompt, then the
decoding scan, one step a generated token after the first.
"""

from benchmark.reduce import xplane

SCAN = "while"


def outermost(device, window):
    """The `while` operations of a device inside the window that lie in
    no other, in the order they ran: [(start, end)]."""
    lo, hi = window
    found = []
    for op in device.ops:       # sorted by start
        if op.category != SCAN or op.start < lo or op.end > hi:
            continue
        if found and op.end <= found[-1][1]:
            continue
        found.append((op.start, op.end))
    return found


def busy_seconds(device, interval):
    """Seconds inside `interval` in which an operation of the device
    ran."""
    return xplane.length(xplane.busy(device, interval))
