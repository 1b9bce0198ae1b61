"""How many times a step the flash-attention forward kernel runs under a
gradient op: the `flash_attention_fwd*` calls whose `op_name` path lies
under a `*_grad` scope (benchmark/reduce/op_scopes.py), over the traced
window's steps, first device.  A gradient that differentiates the
`flash_attention` op as a whole runs the forward again to get its row
statistics back, once an op a step; one that reads what the forward op
saved runs it not at all, and this reads 0.0.  Nothing where the trace
holds no forward call or names no op."""

from benchmark.flops import flash
from benchmark.reduce import op_scopes

LAYER = "ops"
MOVES = "train_items_per_s"
UNIT = "count"
SOURCE = "device_trace"


def read(run):
    scoped = op_scopes.of_run(run)
    if scoped is None:
        return None
    calls = scoped.seconds(
        lambda path: (op_scopes.op_type(path) or "").endswith("_grad"),
        flash.KERNEL_NAME)
    if not calls[True][1] + calls[False][1]:
        return None
    return calls[True][1] / run.facts["traced_steps"]
