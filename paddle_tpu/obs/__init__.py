"""paddle_tpu.obs — the unified observability layer.

One place for the three signals every performance and serving PR reads
(reference: paddle/platform/profiler.h:27-146 wraps every op in a
RecordEvent and parses one global event table — here the same idea is
split into composable pieces instead of one table):

  * `trace`    — thread-safe span tracer with Chrome trace-event JSON
                 export (load the file in Perfetto / chrome://tracing).
                 The executor, both trainer stacks, the parallel layer
                 and the serving engine/batcher all emit spans into it.
  * `registry` — central counter/gauge/histogram registry with labeled
                 metrics, Prometheus-text and JSONL export.
                 `serving/metrics.py` is a thin shim over it, and the
                 serving `/metrics` endpoint serves the unified view.
  * `telemetry`— step-level training telemetry (step time,
                 examples/sec, jit trace/compile counts, host<->device
                 transfer bytes, loss / loss-scale / grad-norm gauges)
                 built on the two above.
  * `health`   — numerics health: jit-safe NaN/Inf + grad-norm
                 monitoring (`NumericsMonitor`), the eager bisection
                 `locate_nonfinite`, and per-segment XLA memory/cost
                 attribution gauges (`xla_*`).
  * `flight`   — crash flight recorder: a bounded ring of structured
                 step records dumped as a JSON post-mortem bundle from
                 executor/trainer/serving exception paths and an
                 excepthook (`obs_dump --flight` renders one).
  * `context`  — request-scoped trace context: W3C-traceparent
                 trace/span ids + request_id with a thread-local
                 current binding, and per-request span recording that
                 survives the serving batcher's thread hop.
  * `tail`     — tail-latency capture: a bounded ring keeping the FULL
                 span tree only for slow/errored requests
                 (`obs_dump --tail` renders a dump; the serving
                 server exposes `/debug/tail`).
  * `fleet`    — fleet-wide aggregation: per-host registry snapshots
                 pushed through the coordinator's TTL-lease store,
                 merged with `host=` labels, with step-time skew and
                 `fleet_straggler{host=}` detection.
  * `mem`      — HBM memory observability: the static liveness
                 timeline (per-op live bytes, top buffers blamed to
                 defining ops) vs XLA's measured `memory_analysis()`
                 actuals, per-segment `mem_*` gauges + drift ratios,
                 the buffer-donation audit, and OOM pre-flight /
                 post-mortems behind `pmem` (tools/mem_cli.py).

Everything is import-cheap and off by default: with tracing disabled a
span is one attribute load + one `is` check, registry counters are
plain locked adds, and the health/flight hooks start with a single
flag/None check — safe on the executor hot path.

`python -m paddle_tpu.tools.obs_dump --selftest` exercises the whole
layer end to end (see docs/OBSERVABILITY.md).
"""

from . import trace
from . import registry
from . import telemetry
from . import health
from . import flight
from . import mem
from . import context
from . import tail
from . import fleet

__all__ = ["trace", "registry", "telemetry", "health", "flight",
           "mem", "context", "tail", "fleet"]
