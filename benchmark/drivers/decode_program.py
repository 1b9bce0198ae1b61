"""A generation cell driven the way a script generates offline in
batches: `fluid.ProgramDecoder` over a key/value-cached step Program,
`decoder.greedy(prompt=<[batch, prompt_len] ids>, max_len=gen_len)` in a
closed loop, one call in flight, each call one lockstep batch that
prefills its prompts and decodes to the end of the model's context.

The decoder is the program's, the step Program is the program's
(benchmark/models/<builder>.py asks for it at the configuration's
widths); the weights, the prompts, the clock and the check are the
benchmark's.  No start-up program runs: the benchmark's seeded weights
go into the scope under the program's names.  The window starts at the
first timed call's dispatch and ends when the last call's tokens are on
the host (`greedy` returns numpy arrays); calls are issued until
`--seconds` have passed and only whole calls count, so
`decode_tok_per_s` is the generated tokens of those calls over the
window's seconds over the cell's chips, prefill inside it, as a caller
pays it.

`correct` is decided once the window has closed and the decoder is
freed, on what the window itself served: every row of one of its calls,
drawn from the seed, against the plain float32 reference
(benchmark/reference/<reference>.py), which makes its own weights from
the same key (`model_key`: the workload file's `weights.seed` where it
states one, else `--seed`; the prompts, the checked rows and the call
picked are `--seed`'s always).  The numbers compared and their limits
are the workload file's `correct`.
"""

import time

import numpy as np

from benchmark import harness


def model_key(run):
    """The key the cell's model is drawn from, all of it (the weights
    served, and the blocks the reference is handed): the workload file's
    `weights.seed` where it states one, `--seed` where it does not.  A
    cell is one model under varying traffic; where a model's speed
    depends on its draw (a router's favourites among the held experts),
    runs on different seeds must not be different models."""
    import jax

    spec = run.workload["weights"]
    if "seed" in spec:
        return jax.random.PRNGKey(spec["seed"])
    return jax.random.PRNGKey(run.seed)


def make_weights(run, model):
    """The parameter tree from `model_key`, on the device, one jitted
    call."""
    import jax

    cfg, spec = run.config, run.workload["weights"]

    def seeded_weights(key):
        return model.weights(cfg, spec, key)

    return jax.block_until_ready(jax.jit(seeded_weights)(model_key(run)))


def serve(run, model):
    """The system under test, ready to generate: the cached step Program
    at the cell's batch, the seeded weights in a scope under the
    program's names (as a script that loads a checkpoint puts them
    there: the start-up program would make 1.6 GB of float32 weights
    that no request reads), a `ProgramDecoder` over them and the empty
    caches every call starts from.  Returns
    `generate(prompt, max_len) -> (tokens, lengths)` on the host."""
    import jax
    import jax.numpy as jnp
    import paddle_tpu.fluid as fluid

    cfg, workload = run.config, run.workload
    with run.clock.phase("build"):
        built = model.build(cfg, workload["batch"])
    scope = fluid.Scope()
    with run.clock.phase("weights"):
        made = make_weights(run, model)
        block = built["main"].global_block()
        names = jax.tree_util.tree_leaves(built["param_names"])
        for name, value in zip(names, jax.tree_util.tree_leaves(made)):
            declared = tuple(block.var(name).shape)
            if declared != value.shape:
                raise ValueError("the program's %r is %s, the seeded "
                                 "weight %s" % (name, declared,
                                                value.shape))
            scope.set(name, value)
        del made
    with run.clock.phase("decoder"):
        decoder = fluid.ProgramDecoder(
            built["main"].clone(for_test=True), token_name="tok",
            logits_name=built["logits"].name,
            state_pairs=built["state_pairs"], scope=scope,
            max_positions=cfg["n_positions"])
    del scope
    # one block of zeros on the host stands for every layer's empty
    # cache, as a caller would make it; the decoder puts each on the
    # device at every call
    empty = np.zeros(built["cache_shape"],
                     jnp.dtype(workload["serve_dtype"]))
    init = {name: empty for name in built["cache_names"]}
    init["pos"] = np.zeros((workload["batch"],), np.int64)
    eos = cfg["vocab_size"]     # outside the vocabulary: no early stop

    def generate(prompt, max_len):
        return decoder.greedy(bos=0, eos=eos, max_len=max_len,
                              init_state=init, prompt=prompt)

    return generate


def trace_lower_seconds():
    """Seconds JAX has spent so far tracing and lowering lambdas, which
    is what `ProgramDecoder` jits: the program's
    `jit_phase_seconds_total`.  The driver reads it before and after the
    decoder's first call, so no other lambda of the process is in the
    difference."""
    from paddle_tpu.obs import telemetry

    counters = telemetry.snapshot()
    return sum(counters.get(
        "jit_phase_seconds_total{fun_name=<lambda>,phase=%s}" % phase, 0.0)
        for phase in ("trace", "lower"))


def window(run, generate, pool, seconds, offset=0):
    """Whole calls until `seconds` have passed: [(pool index, tokens,
    lengths)] and the window's (start, end)."""
    calls = []
    start = time.perf_counter()
    deadline = start + seconds
    while True:
        index = (offset + len(calls)) % len(pool)
        with run.span("bench/generate"):
            tokens, lengths = generate(pool[index],
                                       run.workload["gen_len"])
        calls.append((index, tokens, lengths))
        now = time.perf_counter()
        if now >= deadline:
            return calls, (start, now)


def compare(run, model, pool, call):
    """What `correct` can rest on, for every row of one call: the widest
    and the mean gap by which a served token's reference logit lies
    below the reference's best, and the share of served tokens that are
    not the reference's first (the workload's `correct` says which are
    compared, and with what limit)."""
    import jax

    cfg, workload = run.config, run.workload
    reference = run.lookup.module("reference", workload["reference"])
    params = make_weights(run, model)
    fn = jax.jit(lambda p, prompt, served: reference.gaps(
        cfg, p, prompt, served))
    index, tokens, _ = call
    rows = workload["reference_rows"]
    found = []
    for at in range(0, tokens.shape[0], rows):
        out = fn(params, pool[index][at:at + rows], tokens[at:at + rows])
        found.append(jax.device_get(out))
    del params
    gaps = np.concatenate(found).astype(np.float64)
    # "distinct" is not compared: how varied the served text is
    return {"gap_max": float(gaps.max()), "gap_mean": float(gaps.mean()),
            "not_first_share": float((gaps > 0).mean()),
            "tokens": int(gaps.size),
            "distinct": int(np.unique(tokens).size)}


def check(run, model, pool, calls):
    """{text: ok} for the window's calls."""
    workload, vocab = run.workload, run.config["vocab_size"]
    limits = workload["correct"]
    shape = (workload["batch"], workload["gen_len"])
    sound = [tokens.shape == shape and bool((lengths == shape[1]).all())
             and int(tokens.min()) >= 0 and int(tokens.max()) < vocab
             for _, tokens, lengths in calls]
    run.failed = workload["batch"] * sound.count(False)
    picked = int(np.random.default_rng([run.seed, 0xC0DE]).integers(
        len(calls)))
    checks = {"%d of %d calls gave %d x %d tokens inside the vocabulary, "
              "limit %d" % (sound.count(True), len(calls), shape[0],
                            shape[1], len(calls)): all(sound)}
    if sound[picked]:
        with run.clock.phase("reference"):
            got = compare(run, model, pool, calls[picked])
        print("call %d: %d tokens, %d distinct, %.4f%% not the reference's "
              "first" % (picked, got["tokens"], got["distinct"],
                         100 * got["not_first_share"]), flush=True)
        for name in sorted(set(limits) - {"why"}):
            checks["%s %.6g over the %d tokens of call %d, limit %.6g"
                   % (name, got[name], got["tokens"], picked,
                      limits[name])] = got[name] <= limits[name]
    return checks


def run(run):
    import sys

    workload = run.workload
    model = run.lookup.module("models", workload["builder"])
    with run.clock.phase("prompts"):
        pool = model.prompts(run.config, workload, run.seed)
    generate = serve(run, model)
    before = trace_lower_seconds()
    with run.clock.phase("warmup"):
        generate(pool[0], workload["gen_len"])
    setup = run.compiles.snapshot()
    run.facts.update(setup_compile_s=setup["seconds"],
                     setup_cache_misses=setup["misses"],
                     decode_trace_lower_s=trace_lower_seconds() - before)

    run.start_window()
    calls, (start, end) = window(run, generate, pool, run.seconds, 1)
    compiled = run.compiles.since(setup)["compiles"]
    tokens = sum(t.size for _, t, _ in calls)
    rate = tokens / (end - start) / len(run.devices)
    facts = run.facts
    facts.update(
        calls=len(calls), call_ms=(end - start) / len(calls) * 1e3,
        batch=workload["batch"], prompt_len=workload["prompt_len"],
        gen_len=workload["gen_len"], compiles_in_window=compiled)
    print("window: %d calls in %.3f s, %.1f ms a call, %.2f tok/s per chip"
          % (len(calls), end - start, facts["call_ms"], rate), flush=True)

    if run.trace:
        before = run.compiles.snapshot()
        with run.tracing():
            traced, (t0, t1) = window(run, generate, pool, 0.0,
                                      1 + len(calls))
        calls += traced
        facts["compiles_in_window"] += \
            run.compiles.since(before)["compiles"]
        # not "traced_steps": a call is no training step, and the
        # training cells' readers must find nothing to read here
        facts.update(traced_call_ms=(t1 - t0) * 1e3,
                     traced_step_applications=workload["prompt_len"]
                     + workload["gen_len"] - 1)
        # prefill alone: a call that returns after the prompt's first
        # continuation; a program of its own, so one call to load it
        generate(pool[0], 1)
        t0 = time.perf_counter()
        with run.span("bench/prefill_only"):
            generate(pool[1 % len(pool)], 1)
        facts["prefill_ms"] = (time.perf_counter() - t0) * 1e3
        print("traced call %.1f ms (tracing costs %+.2f%% a call), "
              "prefill alone %.1f ms"
              % (facts["traced_call_ms"],
                 (facts["traced_call_ms"] / facts["call_ms"] - 1) * 100,
                 facts["prefill_ms"]), flush=True)

    facts["memory_peak_bytes"] = harness.memory_peak_bytes(run.devices)
    print("memory: %s" % {k: v for k, v in
                          (run.devices[0].memory_stats() or {}).items()
                          if "peak" in k or "limit" in k}, flush=True)
    del generate
    checks = check(run, model, pool, calls)
    checks["no compile inside the windows (%d), limit 0"
           % facts["compiles_in_window"]] = facts["compiles_in_window"] == 0
    for stream in (sys.stdout, sys.stderr):
        for text, ok in checks.items():
            print("check %s: %s" % ("ok  " if ok else "FAIL", text),
                  file=stream, flush=True)
    run.correct = all(checks.values())
    run.attempted = workload["batch"] * len(calls)
    run.end_to_end["decode_tok_per_s"] = (rate, "tok/s")
    facts["decode_tok_per_s"] = rate
