"""Device milliseconds a step spends in the forward pass: the operations
whose `op_name` path lies under the `jax.named_scope` of an op that is
neither a `*_grad` nor an optimizer's (benchmark/reduce/op_scopes.py).
First device, traced window, over its steps.  Prints the ten op types
with most device time and what lies under no op's scope; forward,
backward, optimizer and unscoped add up to `mxu_ms_per_step` +
`nonmxu_ms_per_step`."""

from benchmark.reduce import op_scopes

LAYER = "ops"
MOVES = "train_items_per_s"
UNIT = "ms"
SOURCE = "device_trace"


def read(run):
    return op_scopes.pass_ms_per_step(run, "forward", report=True)
