"""Device milliseconds a step spends in the state-space layers' own ops,
forward and backward: the operations under the `jax.named_scope` of the
`ssd_scan` and `causal_conv1d` ops and of their gradients
(benchmark/reduce/op_scopes.py), which hold the softplus and the decays'
sums, the chunked scan (its kernels or its chunk products, the carried
state, the reverse walk) and the convolution with its activation.  The
projections around them, the gated norm and the feed-forward are other
ops.  First device, traced window, over its steps.  Prints the op types
apart and, inside `ssd_scan` and its gradient, the scopes the op opens:
`ssd_decay` (softplus, dt A, the sums, the parameters' gradients) and
either `ssd_chunks` (the kernels) or `ssd_intra`, `ssd_state`,
`ssd_inter` (the plain path: a chunk's own products, the chunk states
and their recurrence, what the entering state gives).  A program without
these ops gets no value."""

from benchmark.reduce import op_scopes

LAYER = "state-space layer"
MOVES = "train_items_per_s"
UNIT = "ms"
SOURCE = "device_trace"
SCAN_OPS = ("ssd_scan", "ssd_scan_grad")
CONV_OPS = ("causal_conv1d", "causal_conv1d_grad")
OP_TYPES = SCAN_OPS + CONV_OPS
PHASES = ("ssd_decay", "ssd_chunks", "ssd_intra", "ssd_state", "ssd_inter")
OTHER = "(no scope)"


def phase(path):
    """(op type, the scope the scan op opened) of a path under
    `ssd_scan` or its gradient, else None."""
    parts = [p for p in op_scopes.components(path)
             if not op_scopes.JIT_WRAPPER.match(p)]
    if len(parts) < 2 or parts[0] not in SCAN_OPS:
        return None
    inner = [p for p in parts[1:] if p in PHASES]
    return parts[0], inner[0] if inner else OTHER


def type_seconds(run):
    """{op type: [seconds, calls]} of the four op types, or None."""
    scoped = op_scopes.of_run(run)
    if scoped is None:
        return None
    by_type = scoped.seconds(op_scopes.op_type)
    return {t: by_type[t] for t in OP_TYPES if t in by_type} or None


def read(run):
    found = type_seconds(run)
    steps = run.facts.get("traced_steps")
    if not found or not steps:
        return None
    print("state-space layer: %s" % ", ".join(
        "%s %.3f ms and %.1f operations a step"
        % (t, s / steps * 1e3, calls / steps)
        for t, (s, calls) in found.items()), flush=True)
    phases = op_scopes.of_run(run).seconds(phase)
    phases.pop(None, None)
    print("scan by scope: %s" % ", ".join(
        "%s/%s %.3f ms" % (op, scope, s / steps * 1e3)
        for (op, scope), (s, _) in sorted(phases.items())), flush=True)
    return sum(s for s, _ in found.values()) / steps * 1e3
