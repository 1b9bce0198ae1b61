"""A generation cell over one chip's share of a model whose step carries
**Mamba-2 states it rewrites whole** beside caches of per-position
entries: `fluid.ProgramDecoder` over the share's cached step Program,
`decoder.greedy(prompt=<[batch, prompt_len] ids>, max_len=gen_len)` in a
closed loop, one call in flight, as drivers/decode_state.py drives the
delta-rule share (whose `checked_rows` this uses as it is, with
drivers/decode_share.py's `window` and drivers/decode_program.py's
`model_key`, `make_weights` and `trace_lower_seconds`; read
decode_program.py for the window and the rate, decode_state.py for what
`correct` compares: `gap_mean`, `not_first_share`, `held_part_off`,
`state_off`, `state_off_first`).

What differs from decode_state.py: the builder states the layout the
state is carried in (`state_shapes`: state entries by head lanes, the
kernels' own, which pads nothing) and what a carried row's probes hand
back (`probe_shapes`: the state a head at a time as the reference has
it, the state the last step was handed, and what its scan read);
`correct` compares one number more, `state_step_off`: every mamba
layer's last step held to one float32 update of the state it was handed
(the reference's `state_step_off`), which reads a state kept in a
narrower type in every layer, where `state_off` past the first layer
reads mostly what the stream above it rounded; and the facts have names
of their own (`ssd_state_*`): the readers written for decode_state.py's
cell count a delta-rule share's sizes from its configuration's keys,
which this configuration does not have, and find nothing to read here;
benchmark/reduce/ssd_state_ops.py hands state_ops.py's account of a
traced call a view of the run under the names it knows.
"""

import gc
import sys

import numpy as np

from benchmark import harness


def serve(run, model):
    """`generate(prompt, max_len) -> (tokens, lengths, probes)` on the
    host: decode_state.serve's, for the builder's states and probes."""
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
    of_state = model.probe_shapes(cfg, workload["state_rows"])
    for _, pairs in built["probes"]:
        init.update({pairs["in"][0]: row, pairs["out"][0]: row,
                     pairs["idx"][0]: np.zeros(
                         (batch, cfg["num_experts_per_tok"]), np.int32)})
        init.update({pairs[what][0]: np.zeros(shape, types[kind])
                     for what, (shape, kind) in of_state.items()
                     if what in pairs})
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
    """What `correct` can rest on: decode_state.compare's numbers, and
    `state_step_off`, the largest over the mamba layers of the
    reference's measure of the call's last step alone (under
    `workload["control"]` against the reference made wrong in that way:
    benchmark/tests/ssd_state_control.py)."""
    gc.collect()    # the decoder the caller let go of (decode_share)
    cfg, workload = run.config, run.workload
    if "control" in workload:
        cfg = dict(cfg, control=workload["control"])
    reference = run.lookup.module("reference", workload["reference"])
    ends, block_of = run.lookup.module(
        "drivers", "decode_session").seeded(run, model)
    index, tokens, _, probes = call
    rows = run.lookup.module("drivers", "decode_state").checked_rows(run)
    held, states, steps = {}, {}, {}

    def with_block(layer, block):
        held[layer] = reference.held_part_off(cfg, block, probes[layer])
        if "state_in" in probes[layer]:
            steps[layer] = reference.state_step_off(cfg, block,
                                                    probes[layer])

    def state(layer, want):
        got = probes[layer]["state"]
        states[layer] = reference.state_off(got, want[:got.shape[0]])

    found = reference.gaps(cfg, ends, block_of, pool[index][rows],
                           tokens[rows], workload["reference_rows"],
                           with_block, state)
    gaps = np.asarray(found).astype(np.float64)
    return {"gap_max": float(gaps.max()), "gap_mean": float(gaps.mean()),
            "not_first_share": float((gaps > 0).mean()),
            "held_part_off": max(held.values()),
            "held_part_off_by_layer": [held[k] for k in sorted(held)],
            "state_off": max(states.values()),
            "state_off_first": states[min(states)],
            "state_off_by_layer": [states[k] for k in sorted(states)],
            "state_step_off": max(steps.values()),
            "state_step_off_by_layer": [steps[k] for k in sorted(steps)],
            "tokens": int(gaps.size), "rows": int(rows.size),
            "distinct": int(np.unique(tokens).size)}


def check(run, model, pool, calls):
    """{text: ok} for the window's calls: decode_state.check over this
    module's `compare`."""
    workload, vocab = run.workload, run.config["vocab_size"]
    limits = workload["correct"]
    shape = (workload["batch"], workload["gen_len"])
    sound = [tokens.shape == shape and bool((lengths == shape[1]).all())
             and int(tokens.min()) >= 0 and int(tokens.max()) < vocab
             for _, tokens, lengths, _ in calls]
    run.failed = workload["batch"] * sound.count(False)
    picked = int(np.random.default_rng([run.seed, 0xC0DE]).integers(
        len(calls)))
    checks = {"%d of %d calls gave %d x %d tokens inside the vocabulary, "
              "limit %d" % (sound.count(True), len(calls), shape[0],
                            shape[1], len(calls)): all(sound)}
    if sound[picked]:
        with run.clock.phase("reference"):
            got = compare(run, model, pool, calls[picked])
        by_layer = lambda key: ", ".join("%.3g" % v for v in got[key])
        print("call %d: %d tokens of %d rows, %d distinct in the call, "
              "%.4f%% not the reference's first; by layer, the held "
              "experts' part of the last step off by %s of the "
              "reference's, the recurrent state after it by %s, the last "
              "step's own update of it by %s"
              % (picked, got["tokens"], got["rows"], got["distinct"],
                 100 * got["not_first_share"],
                 by_layer("held_part_off_by_layer"),
                 by_layer("state_off_by_layer"),
                 by_layer("state_step_off_by_layer")), flush=True)
        for name in sorted(set(limits) - {"why"}):
            checks["%s %.6g over the %d tokens of %d rows of call %d, "
                   "limit %.6g" % (name, got[name], got["tokens"],
                                   got["rows"], picked, limits[name])] = \
                got[name] <= limits[name]
    return checks


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
        ssd_state_calls=len(calls),
        ssd_state_call_ms=(end - start) / len(calls) * 1e3,
        ssd_state_batch=workload["batch"],
        ssd_state_prompt_len=prompt_len, ssd_state_gen_len=gen_len,
        compiles_in_window=compiled)
    print("window: %d calls in %.3f s, %.1f ms a call, %.2f tok/s per chip"
          % (len(calls), end - start, facts["ssd_state_call_ms"], rate),
          flush=True)

    if run.trace:
        before = run.compiles.snapshot()
        with run.tracing():
            traced, (t0, t1) = window(run, generate, pool, 0.0,
                                      1 + len(calls))
        calls += traced
        facts["compiles_in_window"] += \
            run.compiles.since(before)["compiles"]
        facts["ssd_state_traced_call_ms"] = (t1 - t0) * 1e3
        print("traced call %.1f ms (tracing costs %+.2f%% a call)"
              % (facts["ssd_state_traced_call_ms"],
                 (facts["ssd_state_traced_call_ms"]
                  / facts["ssd_state_call_ms"] - 1) * 100), flush=True)

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
