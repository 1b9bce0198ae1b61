"""Device milliseconds a step spends adding gradient contributions: the
operations under the `jax.named_scope` of the `sum` ops that
`append_backward` emits where a variable has more than one reader
(benchmark/reduce/op_scopes.py).  With weights shared across depth every
parameter has one contribution per pass, so this is what reading a weight
`total_ut_steps` times costs in the backward; an activation read twice
(the residual stream) has such a `sum` too, unless XLA fused it into a
neighbour, whose path it then carries.  First device, traced window,
over its steps."""

from benchmark.reduce import op_scopes

LAYER = "program"
MOVES = "train_items_per_s"
UNIT = "ms"
SOURCE = "device_trace"
OP_TYPE = "sum"


def read(run):
    scoped = op_scopes.of_run(run)
    if scoped is None:
        return None
    by_type = scoped.seconds(op_scopes.op_type)
    if OP_TYPE not in by_type:
        return None
    seconds, calls = by_type[OP_TYPE]
    steps = run.facts["traced_steps"]
    print("%s: %.1f operations and %.3f ms a step"
          % (OP_TYPE, calls / steps, seconds / steps * 1e3), flush=True)
    return seconds / steps * 1e3
