"""Mean sample rows per executed batch before padding
(`serving_batch_rows`, differenced over the window): how much the
MicroBatcher coalesced."""

LAYER = "serving"
MOVES = "serve_p95_ms"
UNIT = "rows"
SOURCE = "program_counter"


def read(run):
    return run.lookup.module("layer_metrics", "serve_queue_ms_mean").mean(
        run, "serving_batch_rows", 1.0)
