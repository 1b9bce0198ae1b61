"""A generation cell over a dense model's pipeline stage whose step
carries **states it rewrites whole** beside caches of per-position
entries and has no expert layer: `fluid.ProgramDecoder` over the stage's
cached step Program, `decoder.greedy(prompt=<[batch, prompt_len] ids>,
max_len=gen_len)` in a closed loop, one call in flight, as
drivers/decode_state.py drives the expert share with such states (whose
`checked_rows` this imports as it is, with drivers/decode_share.py's
`window`, drivers/decode_program.py's `model_key`, `make_weights` and
`trace_lower_seconds` and drivers/decode_session.py's `seeded`; read
decode_program.py for the window and the rate).

What differs from decode_state.py: nothing is held of an expert layer,
so a call carries no probe of one and `correct` has no `held_part_off`;
the builder states the layout the recurrent state is carried in
(`state_shapes`: heads side by side where a head's values fill no lane
block) and hands the checked rows' states back the heads apart, as the
reference has them.  The facts have names of their own
(`dense_state_*`): the readers written for decode_state.py's cell count
that share's routed experts from its configuration's keys, which this
configuration does not have, and find nothing to read here;
benchmark/reduce/dense_state_ops.py hands the ones that read sizes this
configuration has (the rule's) a view of the run under their names.

`correct`, after the window, over the checked rows of one call: the
served tokens against the float32 reference's full forward
(`gap_mean`, `not_first_share`); `state_off`, each linear layer's
recurrent state after the call's last step, its first `state_rows` rows
(carried out of the decoder as a state pair the step only writes),
against the reference's state after the same tokens position by
position, root mean square of the difference over the reference's, the
largest over the layers; and `state_off_first`, the first layer's alone
(its input is the tokens' embedding, the same on both sides, so its
state differs by this layer's own rounding alone: what tells a state
kept in a narrower type from a sound run).
"""

import gc
import sys

import numpy as np

from benchmark import harness


def serve(run, model):
    """`generate(prompt, max_len) -> (tokens, lengths, probes)` on the
    host: decode_state.serve's, for a step without expert probes."""
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
    # a probe starts as zeros of what the step writes there: the carried
    # rows' states, the heads apart
    apart = (workload["state_rows"], cfg["linear_num_value_heads"],
             cfg["linear_key_head_dim"], cfg["linear_value_head_dim"])
    init.update({feed: np.zeros(apart, np.float32) for feed in probes})
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
    """What `correct` can rest on (the module's docstring)."""
    gc.collect()    # the decoder the caller let go of (decode_share)
    cfg, workload = run.config, run.workload
    if "control" in workload:
        # benchmark/tests/dense_state_control.py: the reference made
        # wrong in one named way, which a limit has to refuse
        cfg = dict(cfg, control=workload["control"])
    reference = run.lookup.module("reference", workload["reference"])
    ends, block_of = run.lookup.module(
        "drivers", "decode_session").seeded(run, model)
    index, tokens, _, probes = call
    rows = run.lookup.module("drivers", "decode_state").checked_rows(run)
    states = {}

    def state(layer, want):
        got = probes[layer]["state"]
        states[layer] = reference.state_off(got, want[:got.shape[0]])

    found = reference.gaps(cfg, ends, block_of, pool[index][rows],
                           tokens[rows], workload["reference_rows"], state)
    gaps = np.asarray(found).astype(np.float64)
    return {"gap_max": float(gaps.max()), "gap_mean": float(gaps.mean()),
            "not_first_share": float((gaps > 0).mean()),
            "state_off": max(states.values()),
            "state_off_first": states[min(states)],
            "state_off_by_layer": [states[k] for k in sorted(states)],
            "tokens": int(gaps.size), "rows": int(rows.size),
            "distinct": int(np.unique(tokens).size)}


def check(run, model, pool, calls):
    """{text: ok} for the window's calls."""
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
        print("call %d: %d tokens of %d rows, %d distinct in the call, "
              "%.4f%% not the reference's first; by linear layer, the "
              "recurrent state after the last step off by %s of the "
              "reference's"
              % (picked, got["tokens"], got["rows"], got["distinct"],
                 100 * got["not_first_share"],
                 ", ".join("%.5f" % v for v in got["state_off_by_layer"])),
              flush=True)
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
        dense_state_calls=len(calls),
        dense_state_call_ms=(end - start) / len(calls) * 1e3,
        dense_state_batch=workload["batch"],
        dense_state_prompt_len=prompt_len, dense_state_gen_len=gen_len,
        compiles_in_window=compiled)
    print("window: %d calls in %.3f s, %.1f ms a call, %.2f tok/s per chip"
          % (len(calls), end - start, facts["dense_state_call_ms"], rate),
          flush=True)

    if run.trace:
        before = run.compiles.snapshot()
        with run.tracing():
            traced, (t0, t1) = window(run, generate, pool, 0.0,
                                      1 + len(calls))
        calls += traced
        facts["compiles_in_window"] += \
            run.compiles.since(before)["compiles"]
        facts["dense_state_traced_call_ms"] = (t1 - t0) * 1e3
        print("traced call %.1f ms (tracing costs %+.2f%% a call)"
              % (facts["dense_state_traced_call_ms"],
                 (facts["dense_state_traced_call_ms"]
                  / facts["dense_state_call_ms"] - 1) * 100), flush=True)

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
