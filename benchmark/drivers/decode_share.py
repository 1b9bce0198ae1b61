"""A generation cell over one chip's share of a model too large for a
chip: `fluid.ProgramDecoder` over the share's cached step Program,
`decoder.greedy(prompt=<[batch, prompt_len] ids>, max_len=gen_len)` in a
closed loop, one call in flight, as drivers/decode_program.py drives the
GPT-2 cell (whose `make_weights` and `trace_lower_seconds` this imports
as they are; read that file for the window and the rate).

What differs, because the share is 9.8 GB of weights and its reference
4 GB a layer in float32: the caches' shape and the positions come from
the builder and the configuration's `serve_positions`; `correct` is
decided on a seeded subset of one call's rows (`checked_rows` of the
workload), against the reference run layer by layer, which is handed one
layer's seeded parameters at a time; and the facts have names of their
own (`share_*`), so that the readers written for the GPT-2-shaped cell
(benchmark/flops/decode.py reads `n_embd`) find nothing to read here.

And a call gives a third thing beside its tokens and lengths: what every
expert layer of the call's last step was handed and gave (the builder's
"probes", carried by the decoder as state pairs the step only writes).
The served tokens cannot tell the held experts' weights from the same
weights rounded to float8 (a near-tied expert changing places between
bfloat16 and float32 moves them more), so `correct` also holds the held
experts' part itself, `held_part_off`, to the reference's routed sum of
the same rows under the same choice of experts.
"""

import gc
import time

import numpy as np

from benchmark import harness


def serve(run, model):
    """`generate(prompt, max_len) -> (tokens, lengths)` on the host: the
    cached step Program at the cell's batch, the seeded weights in a
    scope under the program's names, a `ProgramDecoder` over them (which
    takes the scope's device arrays as they are: nothing is held twice)
    and the empty caches every call starts from."""
    import jax
    import jax.numpy as jnp
    import paddle_tpu.fluid as fluid

    cfg, workload = run.config, run.workload
    shared = run.lookup.module("drivers", "decode_program")
    with run.clock.phase("build"):
        built = model.build(cfg, workload["batch"])
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
    batch = workload["batch"]
    empty = np.zeros(built["cache_shape"],
                     jnp.dtype(workload["serve_dtype"]))
    init = {name: empty for name in built["cache_names"]}
    init["pos"] = np.zeros((batch,), np.int64)
    # a probe starts as zeros of what the step writes there: activations
    # in the weights' type, the router's choice as int32
    row = np.zeros((batch, 1, cfg["hidden_size"]),
                   jnp.dtype(workload["weights"]["dtype"]))
    for _, pairs in built["probes"]:
        init.update({pairs["in"][0]: row, pairs["out"][0]: row,
                     pairs["idx"][0]: np.zeros(
                         (batch, cfg["num_experts_per_tok"]), np.int32)})
    eos = cfg["vocab_size"]     # outside the vocabulary: no early stop

    def generate(prompt, max_len):
        tokens, lengths, last = decoder.greedy(
            bos=0, eos=eos, max_len=max_len, init_state=init,
            prompt=prompt, return_state=sorted(probes))
        return tokens, lengths, {
            layer: {what: last[feed] for what, (feed, _) in pairs.items()}
            for layer, pairs in built["probes"]}

    return generate


def window(run, generate, pool, seconds, offset=0):
    """Whole calls until `seconds` have passed: [(pool index, tokens,
    lengths, probes)] and the window's (start, end); decode_program's
    window, for a `generate` that gives three things."""
    calls = []
    start = time.perf_counter()
    deadline = start + seconds
    while True:
        index = (offset + len(calls)) % len(pool)
        with run.span("bench/generate"):
            calls.append((index,) + generate(pool[index],
                                             run.workload["gen_len"]))
        now = time.perf_counter()
        if now >= deadline:
            return calls, (start, now)


def checked_rows(run):
    """The rows of a call that `correct` reads: `checked_rows` of them,
    drawn from the seed, in order."""
    workload = run.workload
    rng = np.random.default_rng([run.seed, 0x5EED])
    return np.sort(rng.choice(workload["batch"], workload["checked_rows"],
                              replace=False))


def compare(run, model, pool, call):
    """What `correct` can rest on: over the checked rows of one call,
    the widest and the mean gap by which a served token's reference
    logit lies below the reference's best, and the share of served
    tokens that are not the reference's first; and over every row of
    the call's last step, how far each expert layer's held part lies
    from the reference's routed sum of the same input under the same
    choice of experts (`held_part_off`: the largest over the layers)."""
    import jax

    # a decoder and its jitted lambdas refer to each other: collect the
    # one the caller has let go of, or the reference starts with 9.8 GB
    # of weights still held
    gc.collect()
    cfg, workload = run.config, run.workload
    reference = run.lookup.module("reference", workload["reference"])
    spec = workload["weights"]
    key = run.lookup.module("drivers", "decode_program").model_key(run)
    ends = jax.jit(lambda k: model.ends(cfg, spec, model.root(k)))(key)

    def block_of(layer):
        return jax.jit(lambda k: model.block(cfg, spec, model.root(k),
                                             layer))(key)

    index, tokens, _, probes = call
    rows = checked_rows(run)
    off = {}

    def held_part(layer, block):
        if layer in probes:
            off[layer] = reference.held_part_off(cfg, block, probes[layer])

    found = reference.gaps(cfg, ends, block_of, pool[index][rows],
                           tokens[rows], workload["reference_rows"],
                           held_part)
    gaps = np.asarray(jax.device_get(found)).astype(np.float64)
    # "distinct" is not compared: how varied the served text is
    return {"gap_max": float(gaps.max()), "gap_mean": float(gaps.mean()),
            "not_first_share": float((gaps > 0).mean()),
            "held_part_off": max(off.values()),
            "held_part_off_by_layer": [off[k] for k in sorted(off)],
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
              "%.4f%% not the reference's first; the held experts' part "
              "of the last step off by %s of the reference's, by layer"
              % (picked, got["tokens"], got["rows"], got["distinct"],
                 100 * got["not_first_share"],
                 ", ".join("%.5f" % v
                           for v in got["held_part_off_by_layer"])),
              flush=True)
        for name in sorted(set(limits) - {"why"}):
            checks["%s %.6g over the %d tokens of %d rows of call %d, "
                   "limit %.6g" % (name, got[name], got["tokens"],
                                   got["rows"], picked, limits[name])] = \
                got[name] <= limits[name]
    return checks


def run(run):
    import sys

    workload = run.workload
    model = run.lookup.module("models", workload["builder"])
    shared = run.lookup.module("drivers", "decode_program")
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
        share_calls=len(calls),
        share_call_ms=(end - start) / len(calls) * 1e3,
        share_batch=workload["batch"], share_prompt_len=prompt_len,
        share_gen_len=gen_len, compiles_in_window=compiled)
    print("window: %d calls in %.3f s, %.1f ms a call, %.2f tok/s per chip"
          % (len(calls), end - start, facts["share_call_ms"], rate),
          flush=True)

    if run.trace:
        before = run.compiles.snapshot()
        with run.tracing():
            traced, (t0, t1) = window(run, generate, pool, 0.0,
                                      1 + len(calls))
        calls += traced
        facts["compiles_in_window"] += \
            run.compiles.since(before)["compiles"]
        # neither "traced_steps" nor "traced_step_applications": the
        # training cells' and the GPT-2 cell's readers find nothing here
        facts.update(share_traced_call_ms=(t1 - t0) * 1e3,
                     share_step_applications=prompt_len + gen_len - 1)
        # prefill alone: a call that returns after the prompt's first
        # continuation; a program of its own, so one call to load it
        generate(pool[0], 1)
        t0 = time.perf_counter()
        with run.span("bench/prefill_only"):
            generate(pool[1 % len(pool)], 1)
        facts["share_prefill_ms"] = (time.perf_counter() - t0) * 1e3
        print("traced call %.1f ms (tracing costs %+.2f%% a call), "
              "prefill alone %.1f ms"
              % (facts["share_traced_call_ms"],
                 (facts["share_traced_call_ms"] / facts["share_call_ms"]
                  - 1) * 100, facts["share_prefill_ms"]), flush=True)

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
