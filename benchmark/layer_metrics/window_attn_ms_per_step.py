"""Device milliseconds a step spends in the window layers' attention
kernels, forward and backward: the operations under the scope
`attn_window`, which the `flash_attention` op and its gradient op open
around their kernels where the op carries a window
(`paddle_tpu/ops/attention.py`; benchmark/reduce/op_scopes.py).  First
device, traced window, over its steps.  Prints forward and backward
apart.  A program with no window layer gets no value."""

from benchmark.reduce import op_scopes

LAYER = "ops"
MOVES = "train_items_per_s"
UNIT = "ms"
SOURCE = "device_trace"
SCOPE = "attn_window"
MIXED_WITH = "attn_full"


def scope_ms(run, scope):
    """{pass: ms a step} of the operations under `scope`, by whether the
    op type above them is a gradient's; None where there is none."""
    scoped = op_scopes.of_run(run)
    steps = run.facts.get("traced_steps")
    if scoped is None or not steps:
        return None
    found = scoped.seconds(
        lambda path: None if scope not in op_scopes.components(path)
        else "backward" if (op_scopes.op_type(path) or "").endswith("_grad")
        else "forward")
    found.pop(None, None)
    if not found:
        return None
    return {which: s / steps * 1e3 for which, (s, _) in found.items()}


def read(run, scope=SCOPE, other=MIXED_WITH):
    found = scope_ms(run, scope)
    # the split says something only of a program that mixes the two
    if found is None or scope_ms(run, other) is None:
        return None
    print("%s: %s" % (scope, ", ".join(
        "%s %.3f ms a step" % item for item in sorted(found.items()))),
        flush=True)
    return sum(found.values())
