"""Mean milliseconds a batch spent executing, blocked on its results
(`serving_compute_seconds`, differenced over the window)."""

LAYER = "serving"
MOVES = "serve_p50_ms"
UNIT = "ms"
SOURCE = "program_counter"


def read(run):
    return run.lookup.module("layer_metrics", "serve_queue_ms_mean").mean(
        run, "serving_compute_seconds", 1e3)
