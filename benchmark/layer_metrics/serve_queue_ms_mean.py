"""Mean milliseconds a request waited between submission and batch
assembly: sum over count of the server's `serving_queue_seconds`,
differenced over the window (a mean, because a histogram's quantile is
only as fine as its buckets)."""

LAYER = "serving"
MOVES = "serve_p95_ms"
UNIT = "ms"
SOURCE = "program_counter"


def mean(run, name, scale):
    server = run.facts.get("server")
    if not server or not server.get(name + "_count"):
        return None
    return scale * server[name + "_sum"] / server[name + "_count"]


def read(run):
    return mean(run, "serving_queue_seconds", 1e3)
