"""Device milliseconds a step spends in the backward pass: the operations
whose `op_name` path lies under the `jax.named_scope` of a `*_grad` op
(benchmark/reduce/op_scopes.py), the forward that a generic gradient runs
again included.  First device, traced window, over its steps."""

from benchmark.reduce import op_scopes

LAYER = "ops"
MOVES = "train_items_per_s"
UNIT = "ms"
SOURCE = "device_trace"


def read(run):
    return op_scopes.pass_ms_per_step(run, "backward")
