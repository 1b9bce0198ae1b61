"""What no server span covers today: the client's mean time from send to
answer minus the server's mean `serving_total_seconds` over the same
window, which is HTTP, JSON decoding and JSON encoding."""

LAYER = "serving"
MOVES = "serve_p50_ms"
UNIT = "ms"
SOURCE = "host_clock"


def read(run):
    inside = run.lookup.module("layer_metrics", "serve_queue_ms_mean").mean(
        run, "serving_total_seconds", 1e3)
    service = run.facts.get("generator", {}).get("service_mean_ms")
    if inside is None or service is None:
        return None
    return service - inside
