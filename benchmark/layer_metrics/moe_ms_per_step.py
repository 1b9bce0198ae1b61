"""Device milliseconds a step spends in the routed expert layer, forward
and backward: the operations under the `jax.named_scope` of the
`moe_router` and `moe_experts` ops and of their gradients
(benchmark/reduce/op_scopes.py), which hold the router's product,
softmax and top-k, the ordering of the assignments by expert, the
gathers, the grouped products and the weighted combine.  First device,
traced window, over its steps.  Prints the op types apart and, inside
`moe_experts` and its gradient, the scopes the op opens: `moe_route`
(ordering, counts, gathers), `moe_experts` (the grouped products and
the activation) and `moe_combine` (weights, the sum over a token's
rows).  A program without these ops gets no value."""

from benchmark.reduce import op_scopes

LAYER = "ops"
MOVES = "train_items_per_s"
UNIT = "ms"
SOURCE = "device_trace"
OP_TYPES = ("moe_router", "moe_experts", "moe_router_grad",
            "moe_experts_grad")
EXPERT_OPS = ("moe_experts", "moe_experts_grad")
PHASES = ("moe_route", "moe_experts", "moe_combine")
OTHER = "(no scope)"


def phase(path):
    """(op type, the scope the expert op opened) of a path under
    `moe_experts` or its gradient, else None.  The op's type and its
    middle scope share a name, so the scope is looked for after the
    type."""
    parts = [p for p in op_scopes.components(path)
             if not op_scopes.JIT_WRAPPER.match(p)]
    if len(parts) < 2 or parts[0] not in EXPERT_OPS:
        return None
    inner = [p for p in parts[1:] if p in PHASES]
    return parts[0], inner[0] if inner else OTHER


def phase_seconds(run):
    """{(op type, scope): [seconds, calls]} or None."""
    scoped = op_scopes.of_run(run)
    if scoped is None:
        return None
    found = scoped.seconds(phase)
    found.pop(None, None)
    return dict(found)


def read(run):
    scoped = op_scopes.of_run(run)
    if scoped is None:
        return None
    by_type = scoped.seconds(op_scopes.op_type)
    found = {t: by_type[t] for t in OP_TYPES if t in by_type}
    steps = run.facts.get("traced_steps")
    if not found or not steps:
        return None
    print("expert layer: %s" % ", ".join(
        "%s %.3f ms and %.1f operations a step"
        % (t, s / steps * 1e3, calls / steps)
        for t, (s, calls) in found.items()), flush=True)
    print("expert layer by scope: %s" % ", ".join(
        "%s/%s %.3f ms" % (op, scope, s / steps * 1e3)
        for (op, scope), (s, _) in sorted(phase_seconds(run).items())),
        flush=True)
    return sum(s for s, _ in found.values()) / steps * 1e3
