"""The parts of a training cell that do not depend on how the program is
driven: the seeded pool of batches, the reference check, warm-up until a
step compiles nothing, the measured window, the traced window and the
checks on the losses.  A driver (benchmark/drivers/train_*.py) supplies
`step(feeds) -> loss on the device` and `settle()`, which returns when
every update of the last step is in memory.
"""

import time

import numpy as np

from benchmark import harness
from benchmark.flops import program as program_flops


def build(run):
    """The cell's Program at the workload's batch, weights seeded."""
    import paddle_tpu.fluid as fluid

    cfg = run.config
    if cfg["compute_dtype"] == "bfloat16":
        fluid.amp.enable_bf16()
    with run.clock.phase("build"):
        model = run.lookup.module("models", cfg["builder"])
        built = model.build(cfg, run.workload["batch"], train=True)
        built["startup"].random_seed = run.seed
        built["main"].random_seed = run.seed
        built["model"] = model
    return built


def to_master_type(run, names, get, put):
    """Cast the floating-point arrays `get(name)` that are not in the
    configuration's master type to it, and `put` them back.

    Under AMP the start-up program leaves most parameters and optimizer
    slots in bfloat16, and the first two steps turn them into the
    float32 masters every later step carries, each through a program of
    its own that no step of the window runs.  The weights are the
    benchmark's to make, so it makes them in the type they are trained
    in: same values, one step program instead of three to compile, load
    and trace in every run.  (What the three cost: PERF.md section 6.)
    One array at a time, each old one dropped as its cast exists, so
    that set-up holds no more memory than a step does."""
    import jax
    import jax.numpy as jnp

    master = jnp.dtype(run.config["master_dtype"])
    with run.clock.phase("master type"):
        for name in names:
            value = get(name)
            if isinstance(value, jax.Array) and value.dtype != master \
                    and jnp.issubdtype(value.dtype, jnp.floating):
                put(name, value.astype(master))


def make_pool(run, built, sharding=None):
    """`pool` seeded batches made on the device, one jitted call each."""
    import jax

    cfg, batch = run.config, run.workload["batch"]
    with run.clock.phase("data"):
        make = jax.jit(lambda key: built["model"].sample(cfg, batch, key),
                       out_shardings=sharding)
        root = jax.random.fold_in(jax.random.PRNGKey(run.seed), 0x5EED)
        pool = [make(jax.random.fold_in(root, i))
                for i in range(run.workload["pool"])]
        jax.block_until_ready(pool)
    return pool


def reference_loss(run, built, get_param, feeds):
    """The plain reference's loss on `feeds` with the program's own
    start-up weights, read by name through `get_param`."""
    import jax

    cfg = run.config
    with run.clock.phase("reference"):
        reference = run.lookup.module("reference", cfg["reference"])
        params = jax.tree_util.tree_map(get_param, built["param_names"])
        want = float(jax.jit(
            lambda p, f: reference.loss(cfg, p, f))(params, feeds))
        del params
    return want


def read(loss):
    return float(np.asarray(loss).reshape(-1)[0])


def jit_traces():
    """The program's own count of executor jit specialisations."""
    from paddle_tpu.obs import telemetry

    return telemetry.jit_trace_count()


def warm_up(run, step, settle, pool):
    """Steps until one compiles nothing, and at least one pass through
    the pool.  Under AMP the state's dtypes settle only after up to
    three steps, each a program of its own; a step that meets only
    signatures it has seen closes the cycle."""
    losses = []
    with run.clock.phase("warmup"):
        while True:
            before = (run.compiles.compiles, jit_traces())
            losses.append(read(step(pool[len(losses) % len(pool)])))
            settle()
            clean = (run.compiles.compiles, jit_traces()) == before
            if clean and len(losses) >= len(pool):
                break
            if len(losses) > len(pool) + 8:
                raise RuntimeError("warm-up: steps still compile after %d"
                                   % len(losses))
    return losses


def measure(run, step, settle, pool, seconds, offset):
    """Steps for `seconds`, the loss read on the host every
    `loss_read_every`-th step as a script that logs does, ending when
    the last step's updates are in memory.  The deadline is looked at
    only where the host has just waited for the device, so the window
    holds whole groups of steps and overruns by less than one group."""
    every = run.workload["loss_read_every"]
    losses = []
    before = (run.compiles.snapshot(), jit_traces())
    start = time.perf_counter()
    deadline = start + seconds
    while True:
        for _ in range(every):
            with run.span("bench/dispatch"):
                losses.append(
                    step(pool[(offset + len(losses)) % len(pool)]))
        with run.span("bench/loss_read"):
            read(losses[-1])
        if time.perf_counter() >= deadline:
            break
    with run.span("bench/settle"):
        settle()
    end = time.perf_counter()
    return {"steps": len(losses), "seconds": end - start,
            "losses": [read(v) for v in losses],
            "compiles": run.compiles.since(before[0])["compiles"]
            + jit_traces() - before[1]}


def run_windows(run, built, step, settle, pool, want, after=None):
    """Warm-up, the measured window and, in a traced run, a short traced
    window and `after(facts)`; fills the run's results."""
    warm = warm_up(run, step, settle, pool)
    setup = run.compiles.snapshot()
    run.start_window()
    window = measure(run, step, settle, pool, run.seconds, len(warm))
    items = built["items_per_step"]
    rate = window["steps"] * items / window["seconds"] / len(run.devices)
    facts = run.facts
    facts.update(
        step_ms=window["seconds"] / window["steps"] * 1e3,
        items_per_step=items, chips=len(run.devices),
        compiles_in_window=window["compiles"],
        setup_compile_s=setup["seconds"], setup_cache_misses=setup["misses"])
    print("window: %d steps in %.3f s, %.3f ms/step, %.2f %ss/s per chip"
          % (window["steps"], window["seconds"], facts["step_ms"], rate,
             run.config["item"]), flush=True)

    losses = warm + window["losses"]
    if run.trace:
        with run.tracing():
            traced = measure(run, step, settle, pool,
                             run.workload.get("trace_seconds", 3.0),
                             len(losses))
        losses += traced["losses"]
        facts.update(traced_steps=traced["steps"],
                     traced_step_ms=traced["seconds"] / traced["steps"]
                     * 1e3)
        facts["compiles_in_window"] += traced["compiles"]
        print("traced window: %d steps in %.3f s, %.3f ms/step (tracing "
              "costs %+.2f%% a step)"
              % (traced["steps"], traced["seconds"],
                 facts["traced_step_ms"],
                 (facts["traced_step_ms"] / facts["step_ms"] - 1) * 100),
              flush=True)
        facts["flops"] = program_flops.program_flops(built["main"])
        if after is not None:
            after(facts)

    tol = run.config["reference_tolerance"]["loss_rel"]
    off = abs(warm[0] - want) / abs(want)
    n = len(pool)
    first, last = float(np.mean(losses[:n])), float(np.mean(losses[-n:]))
    checks = {
        "loss %.6f within %.1e of the reference's %.6f (off by %.2e)"
        % (warm[0], tol, want, off): off <= tol,
        "every loss finite": bool(np.isfinite(losses).all()),
        "mean loss of the last pass through the pool %.4f below the "
        "first's %.4f" % (last, first): last < first,
    }
    for text, ok in checks.items():
        print("check %s: %s" % ("ok  " if ok else "FAIL", text), flush=True)
    run.correct = all(checks.values())
    run.attempted = len(losses)
    run.failed = int(np.sum(~np.isfinite(losses)))
    run.end_to_end["train_items_per_s"] = (rate, "items/s")
    facts["train_items_per_s"] = rate
    facts["memory_peak_bytes"] = harness.memory_peak_bytes(run.devices)
