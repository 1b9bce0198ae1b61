"""How late the benchmark's own generator ran: the 95th percentile of
actual send time minus due time, on the generator's clock.  A starved
generator reads as a fast server."""

LAYER = "entry"
MOVES = "serve_p95_ms"
UNIT = "ms"
SOURCE = "host_clock"


def read(run):
    return run.facts.get("generator", {}).get("late_p95_ms")
