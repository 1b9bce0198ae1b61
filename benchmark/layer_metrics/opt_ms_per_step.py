"""Device milliseconds a step spends in the optimizer: the operations
whose `op_name` path lies under the `jax.named_scope` of an optimizer's op
(`sgd`, `momentum`, `adam`, ...: the `op_type` of
`fluid.optimizer.Optimizer`'s subclasses; benchmark/reduce/op_scopes.py).
First device, traced window, over its steps."""

from benchmark.reduce import op_scopes

LAYER = "ops"
MOVES = "train_items_per_s"
UNIT = "ms"
SOURCE = "device_trace"


def read(run):
    return op_scopes.pass_ms_per_step(run, "optimizer")
