"""Seconds JAX spent tracing the program's step functions in Python and
lowering them to MLIR: the program's `jit_phase_seconds_total` counter
(`paddle_tpu/obs/telemetry.py`, from `jax.monitoring`), phases `trace` and
`lower`, for the executor's `segment_fn` and the trainers' `step`.  This
is the part of set-up a warm compile cache cannot save; all of it is
set-up while `compiles_in_window` is 0."""

LAYER = "program"
MOVES = "setup_s"
UNIT = "s"
SOURCE = "program_counter"
KEY = "jit_phase_seconds_total{fun_name=%s,phase=%s}"


def read(run):
    from paddle_tpu.obs import telemetry

    counters = telemetry.snapshot()
    found = [counters[KEY % (fun, phase)]
             for fun in ("segment_fn", "step")
             for phase in ("trace", "lower")
             if KEY % (fun, phase) in counters]
    return sum(found) if found else None
