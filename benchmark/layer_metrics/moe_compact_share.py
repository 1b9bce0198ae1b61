"""Of the device time a traced window spends in the two row paths of
`moe_experts` and its gradient where the op holds a range of the experts
scored and orders enough rows to have both (`paddle_tpu/ops/moe.py`:
`lax.cond`s on the held rows' count against a bound from the shapes),
the percentage under the scope `moe_compact`, the path over the bound's
rows; the rest lies under `moe_all_rows`, the path over every
assignment's row.  Only the body that ran has device time, so this says
how often the compact path engaged in the window: 100 when every
application of every layer took it, 0 when none did.  The op's `Counts`
are not read in a timed run; this is.  First device, traced window.
Prints each path's milliseconds a step.  A program whose expert ops have
one body (no range, or under 32768 rows) gets no value."""

from benchmark.reduce import op_scopes

LAYER = "ops"
MOVES = "train_items_per_s"
UNIT = "%"
SOURCE = "device_trace"
COMPACT, ALL_ROWS = "moe_compact", "moe_all_rows"


def branch(path):
    """The row path an operation lies under, or None."""
    parts = op_scopes.components(path)
    return next((p for p in parts if p in (COMPACT, ALL_ROWS)), None)


def read(run):
    scoped = op_scopes.of_run(run)
    if scoped is None:
        return None
    found = scoped.seconds(branch)
    found.pop(None, None)
    if not found:
        return None
    compact, rest = (found[b][0] if b in found else 0.0
                     for b in (COMPACT, ALL_ROWS))
    steps = run.facts["traced_steps"]
    print("expert rows: %s %.3f ms a step, %s %.3f ms a step"
          % (COMPACT, compact / steps * 1e3, ALL_ROWS, rest / steps * 1e3),
          flush=True)
    return 100.0 * compact / (compact + rest)
