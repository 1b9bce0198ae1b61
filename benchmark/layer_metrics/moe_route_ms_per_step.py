"""Device milliseconds a step spends moving rows around the experts: the
operations under the `moe_route` and `moe_combine` scopes of the
`moe_experts` op and of its gradient (ordering the assignments by
expert, counting them, gathering the tokens into that order and each
token's rows back out of it, the weighted sums): the memory-bound part
of the layer, which a dense feed-forward has none of.  First device,
traced window, over its steps; forward and backward printed apart."""

LAYER = "ops"
MOVES = "train_items_per_s"
UNIT = "ms"
SOURCE = "device_trace"
SCOPES = ("moe_route", "moe_combine")


def read(run):
    times = run.lookup.module("layer_metrics", "moe_ms_per_step")
    found = times.phase_seconds(run)
    steps = run.facts.get("traced_steps")
    if not found or not steps:
        return None
    moved = {key: s for key, (s, _) in found.items() if key[1] in SCOPES}
    if not moved:
        return None
    print("moving rows: %s" % ", ".join(
        "%s/%s %.3f ms a step" % (op, scope, s / steps * 1e3)
        for (op, scope), s in sorted(moved.items())), flush=True)
    return sum(moved.values()) / steps * 1e3
