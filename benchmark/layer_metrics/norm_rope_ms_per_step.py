"""Device milliseconds a step spends in the `rms_norm` and `rope` ops
and their gradients: the operations under those ops' `jax.named_scope`s
(benchmark/reduce/op_scopes.py).  A fusion has the path of its root, so
what XLA fused onto a norm counts here and a norm fused into a matrix
product does not.  First device, traced window, over its steps.  Prints
the four apart."""

from benchmark.reduce import op_scopes

LAYER = "ops"
MOVES = "train_items_per_s"
UNIT = "ms"
SOURCE = "device_trace"
OP_TYPES = ("rms_norm", "rms_norm_grad", "rope", "rope_grad")


def seconds(run):
    """{op type: [seconds, operations]} in the traced window, or None."""
    scoped = op_scopes.of_run(run)
    if scoped is None:
        return None
    by_type = scoped.seconds(op_scopes.op_type)
    return {t: by_type[t] for t in OP_TYPES if t in by_type} or None


def read(run):
    found = seconds(run)
    if found is None:
        return None
    steps = run.facts["traced_steps"]
    print("norm and rope: %s" % ", ".join(
        "%s %.3f ms and %.1f operations a step"
        % (t, s / steps * 1e3, calls / steps)
        for t, (s, calls) in found.items()), flush=True)
    return sum(s for s, _ in found.values()) / steps * 1e3
