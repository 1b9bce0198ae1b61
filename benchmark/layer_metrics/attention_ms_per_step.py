"""Device milliseconds a step spends in attention, forward and backward:
the operations under the `jax.named_scope` of the `flash_attention` op
and of its gradient (benchmark/reduce/op_scopes.py), which hold the
kernel, the split-head copies around it, the forward run again by the
generic gradient and the backward scan.  First device, traced window,
over its steps.  Prints the two apart."""

from benchmark.reduce import op_scopes

LAYER = "ops"
MOVES = "train_items_per_s"
UNIT = "ms"
SOURCE = "device_trace"
OP_TYPES = ("flash_attention", "flash_attention_grad")


def read(run):
    scoped = op_scopes.of_run(run)
    if scoped is None:
        return None
    by_type = scoped.seconds(op_scopes.op_type)
    found = {t: by_type[t] for t in OP_TYPES if t in by_type}
    if not found:
        return None
    steps = run.facts["traced_steps"]
    print("attention: %s" % ", ".join(
        "%s %.3f ms and %.1f operations a step"
        % (t, s / steps * 1e3, calls / steps)
        for t, (s, calls) in found.items()), flush=True)
    return sum(s for s, _ in found.values()) / steps * 1e3
