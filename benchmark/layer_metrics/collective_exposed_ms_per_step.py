"""The part of collective_ms_per_step in which no other operation ran on
that device: communication that nothing hides."""

LAYER = "multichip"
MOVES = "train_items_per_s"
UNIT = "ms"
SOURCE = "device_trace"


def read(run):
    found = run.lookup.module(
        "layer_metrics", "collective_ms_per_step").seconds(run)
    if found is None:
        return None
    return found[1] / run.facts["traced_steps"] * 1e3
