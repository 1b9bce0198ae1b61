"""Peak device memory in use on the fullest chip, after the window."""

LAYER = "device"
MOVES = "train_items_per_s"
UNIT = "GiB"
SOURCE = "program_counter"


def read(run):
    peak = run.facts.get("memory_peak_bytes")
    return peak / 2.0 ** 30 if peak else None
