"""A generation cell over one chip's share of a model whose step carries
**three kinds of state**: a convolution tail and a float32 recurrent
state it rewrites whole (the delta rule's layers) beside a cache of
latents it appends to (the latent-attention layer), and whose leading
layers are dense: `fluid.ProgramDecoder` over the share's cached step
Program, `decoder.greedy(prompt=<[batch, prompt_len] ids>,
max_len=gen_len)` in a closed loop, one call in flight.  What
drivers/decode_state.py is for the step with two kinds of state, and
that file's code where it serves as it is (its `check` and `compare`, a
control's `workload["control"]` included: benchmark/tests/
hybrid_control.py; with
drivers/decode_share.py's `window`, drivers/decode_program.py's
`model_key`, `make_weights` and `trace_lower_seconds` and
drivers/decode_session.py's `seeded`; read decode_program.py for the
window and the rate, decode_state.py for what `correct` compares and
why).

What differs.  A layer's probes are what the layer has: an expert
layer's three ("in", "idx", "out"), a delta-rule layer's "state", and a
dense delta-rule layer has the state alone (decode_state.py takes every
layer for an expert layer).  The facts have names of their own
(`hybrid_*`), so that the readers written for the other generation
cells find nothing to read here, and this cell's readers
(benchmark/reduce/hybrid_ops.py) nothing in theirs.

`correct`, after the window, over the checked rows of one call, is
decode_state.py's: `gap_mean`, `not_first_share`, `held_part_off` (the
worst expert layer), `state_off` (the worst delta-rule layer) and
`state_off_first` (layer 0's, whose input is the tokens' embedding).
"""

import sys

import numpy as np

from benchmark import harness


def serve(run, model):
    """`generate(prompt, max_len) -> (tokens, lengths, probes)` on the
    host: decode_state.serve's, for probes a layer may lack."""
    import jax
    import jax.numpy as jnp
    import paddle_tpu.fluid as fluid

    cfg, workload = run.config, run.workload
    shared = run.lookup.module("drivers", "decode_program")
    batch = workload["batch"]
    with run.clock.phase("build"):
        built = model.build(cfg, batch, workload["state_rows"])
    scope = fluid.Scope()
    with run.clock.phase("weights"):
        made = shared.make_weights(run, model)
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
    probes = {feed: out for _, pairs in built["probes"]
              for feed, out in pairs.values()}
    with run.clock.phase("decoder"):
        decoder = fluid.ProgramDecoder(
            built["main"].clone(for_test=True), token_name="tok",
            logits_name=built["logits"].name,
            state_pairs=built["state_pairs"] + list(probes.items()),
            scope=scope, max_positions=cfg["serve_positions"])
    del scope
    weights = jnp.dtype(workload["weights"]["dtype"])
    types = {"state": np.dtype("float32"), "tail": weights,
             "cache": jnp.dtype(workload["serve_dtype"])}
    init = {"pos": np.zeros((batch,), np.int64)}
    init.update({feed: np.zeros(shape, types[kind])
                 for feed, (shape, kind) in built["state_shapes"].items()})
    # a probe starts as zeros of what the step writes there
    row = np.zeros((batch, 1, cfg["hidden_size"]), weights)
    for layer, pairs in built["probes"]:
        if "in" in pairs:
            init.update({pairs["in"][0]: row, pairs["out"][0]: row,
                         pairs["idx"][0]: np.zeros(
                             (batch, cfg["num_experts_per_tok"]),
                             np.int32)})
        if "state" in pairs:
            init[pairs["state"][0]] = np.zeros(
                (workload["state_rows"],)
                + built["state_shapes"]["delta_state_%d" % layer][0][1:],
                np.float32)
    eos = cfg["vocab_size"]     # outside the vocabulary: no early stop

    def generate(prompt, max_len):
        tokens, lengths, last = decoder.greedy(
            bos=0, eos=eos, max_len=max_len, batch_size=batch,
            init_state=init, prompt=prompt, return_state=sorted(probes))
        return tokens, lengths, {
            layer: {what: last[feed] for what, (feed, _) in pairs.items()}
            for layer, pairs in built["probes"]}

    return generate


def compare(run, model, pool, call):
    """decode_state.compare's, under this driver's name (what
    benchmark/tests/hybrid_control.py asks a driver for)."""
    return run.lookup.module("drivers", "decode_state").compare(
        run, model, pool, call)


def run(run):
    workload = run.workload
    model = run.lookup.module("models", workload["builder"])
    shared = run.lookup.module("drivers", "decode_program")
    window = run.lookup.module("drivers", "decode_share").window
    gen_len, prompt_len = workload["gen_len"], workload["prompt_len"]
    with run.clock.phase("prompts"):
        pool = model.prompts(run.config, workload, run.seed)
    generate = serve(run, model)
    before = shared.trace_lower_seconds()
    with run.clock.phase("warmup"):
        generate(pool[0], gen_len)
    setup = run.compiles.snapshot()
    run.facts.update(setup_compile_s=setup["seconds"],
                     setup_cache_misses=setup["misses"],
                     decode_trace_lower_s=shared.trace_lower_seconds()
                     - before)

    run.start_window()
    calls, (start, end) = window(run, generate, pool, run.seconds, 1)
    compiled = run.compiles.since(setup)["compiles"]
    tokens = sum(call[1].size for call in calls)
    rate = tokens / (end - start) / len(run.devices)
    facts = run.facts
    facts.update(
        hybrid_calls=len(calls),
        hybrid_call_ms=(end - start) / len(calls) * 1e3,
        hybrid_batch=workload["batch"], hybrid_prompt_len=prompt_len,
        hybrid_gen_len=gen_len, compiles_in_window=compiled)
    print("window: %d calls in %.3f s, %.1f ms a call, %.2f tok/s per chip"
          % (len(calls), end - start, facts["hybrid_call_ms"], rate),
          flush=True)

    if run.trace:
        before = run.compiles.snapshot()
        with run.tracing():
            traced, (t0, t1) = window(run, generate, pool, 0.0,
                                      1 + len(calls))
        calls += traced
        facts["compiles_in_window"] += \
            run.compiles.since(before)["compiles"]
        facts["hybrid_traced_call_ms"] = (t1 - t0) * 1e3
        print("traced call %.1f ms (tracing costs %+.2f%% a call)"
              % (facts["hybrid_traced_call_ms"],
                 (facts["hybrid_traced_call_ms"] / facts["hybrid_call_ms"]
                  - 1) * 100), flush=True)

    facts["memory_peak_bytes"] = harness.memory_peak_bytes(run.devices)
    print("memory: %s" % {k: v for k, v in
                          (run.devices[0].memory_stats() or {}).items()
                          if "peak" in k or "limit" in k}, flush=True)
    del generate
    checks = run.lookup.module("drivers", "decode_state").check(
        run, model, pool, calls)
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
