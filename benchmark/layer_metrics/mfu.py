"""Model FLOP/s utilisation: items per second per chip times the static
FLOPs an item requires (benchmark/flops/program.py on the built IR), over
the chip's published bf16 peak.  Recomputation is not counted."""

LAYER = "program"
MOVES = "train_items_per_s"
UNIT = "%"
SOURCE = "host_clock"


def read(run):
    facts = run.facts
    if run.peaks is None or "flops" not in facts:
        return None
    per_item = facts["flops"]["total"] / facts["items_per_step"]
    return 100.0 * facts["train_items_per_s"] * per_item \
        / run.peaks["bf16_flops_per_s"]
