"""Device milliseconds a step spends in the flash-attention backward: the
operations under the `flash_attention_bwd` scope that
`kernels/flash_attention.py:_flash_bwd_rule` opens
(benchmark/reduce/op_scopes.py).  First device, traced window, over its
steps.  Prints how many of a step's `flash_attention_fwd` kernel calls lie
under a `*_grad` scope: those are the forward run again by the op's
generic gradient."""

from benchmark.flops import flash
from benchmark.reduce import op_scopes

LAYER = "kernels"
MOVES = "train_items_per_s"
UNIT = "ms"
SOURCE = "device_trace"
SCOPE = "flash_attention_bwd"


def read(run):
    scoped = op_scopes.of_run(run)
    if scoped is None:
        return None
    seconds, calls = scoped.under(SCOPE)
    if not calls:
        return None
    steps = run.facts["traced_steps"]
    forward = scoped.seconds(
        lambda path: (op_scopes.op_type(path) or "").endswith("_grad"),
        flash.KERNEL_NAME)
    total = sum(c for _, c in forward.values())
    print("%s: %.1f operations and %.3f ms a step; %s: %.1f of a step's "
          "%.1f calls lie under a *_grad scope"
          % (SCOPE, calls / steps, seconds / steps * 1e3, flash.KERNEL_NAME,
             forward[True][1] / steps, total / steps), flush=True)
    return seconds / steps * 1e3
