"""A generation cell over one chip of a decode pool whose sessions are
*long* and whose step keeps two kinds of cache: every row of a call
starts with a session already in its caches (a full layer's keys and
values over the whole session, a window layer's last `sliding_window`
positions in a ring), takes a further turn (a question through the
step's own prefill scan) and decodes a long answer,
`decoder.greedy(prompt=<[batch, prompt_len] ids>, max_len=gen_len,
init_state=<the session's caches and its position>)` in a closed loop,
one call in flight, as drivers/decode_session.py drives the sparse
latent cell.  `window` and `checked_rows` are drivers/decode_share.py's
and `model_key`, `make_weights` and `trace_lower_seconds`
drivers/decode_program.py's, imported as they are (read those files for
the window and the rate).

What differs.  The session is of two cache kinds, handed over at the
extents the step Program declares (`ProgramDecoder` holds each feed to
its own).  Set-up makes it with the cell's plain reference a turn of
positions at a time (benchmark/reference/exaone_moe.py: float32, rounded
once to the caches' type; rows that ask of one document share its
session), before the served weights are on the device, and the same
compiled reference layers then continue from the session's own float32
keys and values for `correct`: one turn more, the question and the
served tokens.  The facts have names of their own (`long_*`), so that
the readers written for the other generation cells find nothing to read
here.

`correct`, after the window, over the checked rows of one call: the
served tokens against the reference's full forward over document,
question and served tokens (`gap_mean`, `not_first_share`, as the share
cells'); and of the call's last step, carried out of the decoder as
state pairs the step only writes, each layer judged on the input the
program itself gave it: the attention sub-layer's output against the
reference's, as the root mean square of the difference over the
reference's, the worst window layer and the worst full layer apart
(`attn_off_window`, `attn_off_full`) and the first layer's alone
(`attn_off_first`), and the share cells' `held_part_off`.  A layer past
the first attends slots the call itself wrote (the question and the
answer so far: all 128 of a window layer's ring, 1023 of a full layer's
32,767), and what the program wrote there has drifted from the
reference's own forward by every rounding upstream: `attn_off_window`
holds that too.  The first layer's input is the tokens' embedding, the
same on both sides, so its ring differs by rounding alone:
`attn_off_first` is what says a ring written a slot off apart from a
sound run.
"""

import gc
import time

import numpy as np

from benchmark import harness

WINDOW = "sliding_attention"


def seeded(run, model):
    """drivers/decode_session.py's: (`ends`, `block_of(layer)`), the
    parameters `make_weights` serves as the reference asks for them."""
    return run.lookup.module("drivers", "decode_session").seeded(run, model)


def make_session(run, model, documents):
    """({cache feed: the step's declared shape in the cache's type,
    "pos": [batch]} on the host: what every call starts from; and what
    `compare` continues the reference from after the window: the
    reference's compiled layers and, for the documents the checked rows
    ask of, the float32 keys and values of every layer, 0.27 GB a full
    layer and document on the host)."""
    import jax.numpy as jnp

    cfg, workload = run.config, run.workload
    reference = run.lookup.module("reference", workload["reference"])
    share = run.lookup.module("drivers", "decode_share")
    ends, block_of = seeded(run, model)
    batch, each = workload["batch"], workload["questions_a_document"]
    layers = reference.Layers(cfg, workload["reference_query_block"])
    made, kept = reference.session(
        cfg, layers, ends, block_of, documents,
        workload["prompt_len"] + workload["gen_len"],
        keep=set(share.checked_rows(run) // each))
    length = documents.shape[1]
    dtype = jnp.dtype(workload["serve_dtype"])
    # a control serves a narrower ring: the last `window` of the
    # positions the reference's ring holds, each in its slot of that ring
    window = workload.get("window", cfg["sliding_window"])
    init = {"pos": np.full((batch,), length, np.int64)}
    for layer, pair in enumerate(made):
        for which, value in zip("kv", pair):
            if cfg["layer_types"][layer] == WINDOW \
                    and window != value.shape[2]:
                at = np.arange(length - window, length)
                narrow = np.zeros(value.shape[:2] + (window,)
                                  + value.shape[3:], value.dtype)
                narrow[:, :, at % window] = value[:, :, at % value.shape[2]]
                value = narrow
            # float32 out of the reference; a cache rounds once, to its type
            init["%s_cache_%d" % (which, layer)] = np.repeat(
                value.astype(dtype), each, axis=0)
    return init, (layers, kept)


def build(run, model):
    """The builder's step at the cell's batch.  First in a run, before
    the session is made: a program that cannot build this step (the
    parent commit's) fails here, at once."""
    cfg, workload = run.config, run.workload
    changed = {}
    if "window" in workload:    # a control of `correct`
        changed["window"] = workload["window"]
    with run.clock.phase("build"):
        return model.build(cfg, workload["batch"], **changed)


def serve(run, model, init, built):
    """`generate(prompt, max_len) -> (tokens, lengths, probes)` on the
    host: `build`'s step Program at the cell's batch, the seeded weights
    in a scope under the program's names, a `ProgramDecoder` over them,
    and the session every call starts from."""
    import jax
    import jax.numpy as jnp
    import paddle_tpu.fluid as fluid

    cfg, workload = run.config, run.workload
    shared = run.lookup.module("drivers", "decode_program")
    by_kind = {}
    for feed, shape in built["cache_shapes"].items():
        if init[feed].shape != shape:
            raise ValueError("the session's %r is %s, the program's %s"
                             % (feed, init[feed].shape, shape))
        kind = "window" if shape[2] != cfg["serve_positions"] else "full"
        by_kind[kind] = by_kind.get(kind, 0) + init[feed].nbytes
    run.facts["long_cache_bytes"] = by_kind
    print("session as handed in: %s"
          % ", ".join("%s caches %.4f GB" % (kind, size / 1e9)
                      for kind, size in sorted(by_kind.items())),
          flush=True)
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
    init = dict(init)
    # a probe starts as zeros of what the step writes there: activations
    # in the weights' type, the router's choice as int32
    row = np.zeros((batch, 1, cfg["hidden_size"]),
                   jnp.dtype(workload["weights"]["dtype"]))
    for _, pairs in built["probes"]:
        init.update({feed: np.zeros((batch, cfg["num_experts_per_tok"]),
                                    np.int32) if what == "idx" else row
                     for what, (feed, _) in pairs.items()})
    eos = cfg["vocab_size"]     # outside the vocabulary: no early stop

    def generate(prompt, max_len):
        tokens, lengths, last = decoder.greedy(
            bos=0, eos=eos, max_len=max_len, init_state=init,
            prompt=prompt, return_state=sorted(probes))
        return tokens, lengths, {
            layer: {what: last[feed] for what, (feed, _) in pairs.items()}
            for layer, pairs in built["probes"]}

    return generate


def compare(run, model, documents, pool, call, inputs):
    """What `correct` can rest on (`inputs`: `make_session`'s second):
    over the checked rows of one call, the mean gap by which a served
    token's reference logit lies below the reference's best and the
    share of served tokens that are not the reference's first; and of
    the call's last step, each layer on the program's own input to it:
    the distance of its attention output from the reference's, as the
    root mean square of the difference over the reference's, the largest
    over the window layers, over the full layers, and the first layer's,
    all over the checked rows; and over every row `held_part_off`."""
    import jax

    gc.collect()    # the decoder the caller has let go of: 7.7 GB
    cfg, workload = run.config, run.workload
    share = run.lookup.module("drivers", "decode_share")
    reference = run.lookup.module("reference", workload["reference"])
    ends, block_of = seeded(run, model)
    layers, kept = inputs
    index, tokens, _, probes = call
    rows = share.checked_rows(run)
    each = workload["questions_a_document"]
    turn = np.concatenate([pool[index][rows], tokens[rows]], axis=1)
    start = documents.shape[1]
    # the call's last step read the token before the last served one,
    # at the position before the last
    last = {"at": start + turn.shape[1] - 2,
            "attn_in": [probes[k]["attn_in"][rows][:, 0]
                        for k in sorted(probes)]}
    off = {}

    def held_part(layer, block):
        if "idx" in probes[layer]:
            off[layer] = reference.held_part_off(cfg, block, probes[layer])

    found, step = reference.gaps(
        cfg, layers, ends, block_of, turn, start, pool.shape[2] - 1,
        tokens[rows], last, held_part,
        [kept[int(d)] for d in rows // each])
    gaps = np.asarray(jax.device_get(found)).astype(np.float64)
    attn_off = []
    for k, want in zip(sorted(probes), step["attn"]):
        want = np.asarray(want, np.float64)
        got = np.asarray(probes[k]["attn_out"][rows][:, 0], np.float64)
        attn_off.append(float(np.sqrt(np.mean(np.square(got - want))
                                      / np.mean(np.square(want)))))
    by_kind = {kind: max(v for v, t in zip(attn_off, cfg["layer_types"])
                         if (t == WINDOW) == (kind == "window"))
               for kind in ("window", "full")}
    # "distinct" is not compared: how varied the served text is
    return {"gap_max": float(gaps.max()), "gap_mean": float(gaps.mean()),
            "not_first_share": float((gaps > 0).mean()),
            "attn_off_window": by_kind["window"],
            "attn_off_full": by_kind["full"], "attn_off_first": attn_off[0],
            "attn_off_by_layer": attn_off,
            "held_part_off": max(off.values()),
            "held_part_off_by_layer": [off[k] for k in sorted(off)],
            "tokens": int(gaps.size), "rows": int(rows.size),
            "distinct": int(np.unique(tokens).size)}


def check(run, model, documents, pool, calls, inputs):
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
            got = compare(run, model, documents, pool, calls[picked],
                          inputs)
        print("call %d: %d tokens of %d rows, %d distinct in the call, "
              "%.4f%% not the reference's first; of the last step, by "
              "layer (%s): the attention is off by %s, the held experts' "
              "part by %s"
              % (picked, got["tokens"], got["rows"], got["distinct"],
                 100 * got["not_first_share"],
                 "".join("L" if t == WINDOW else "G"
                         for t in run.config["layer_types"]),
                 ", ".join("%.5f" % v for v in got["attn_off_by_layer"]),
                 ", ".join("%.5f" % v
                           for v in got["held_part_off_by_layer"])),
              flush=True)
        for name in sorted(set(limits) - {"why"}):
            checks["%s %.6g over %d rows of call %d, limit %.6g"
                   % (name, got[name], got["rows"], picked,
                      limits[name])] = got[name] <= limits[name]
    return checks


def run(run):
    import sys

    import jax

    cfg, workload = run.config, run.workload
    model = run.lookup.module("models", workload["builder"])
    share = run.lookup.module("drivers", "decode_share")
    shared = run.lookup.module("drivers", "decode_program")
    gen_len, prompt_len = workload["gen_len"], workload["prompt_len"]
    session_len = workload["session_len"]
    # what `ProgramDecoder._check_extent` cannot see: the position the
    # call starts from lies inside init_state
    if session_len + prompt_len + gen_len > cfg["serve_positions"] \
            or session_len % (prompt_len + gen_len) \
            or workload["batch"] != workload["documents"] \
            * workload["questions_a_document"]:
        raise SystemExit(
            "benchmark: a session of %d positions, a prompt of %d and %d "
            "generated tokens do not fit %d cache positions, the session "
            "is not whole turns of prompt and answer, or %d rows are not "
            "%d documents x %d questions"
            % (session_len, prompt_len, gen_len, cfg["serve_positions"],
               workload["batch"], workload["documents"],
               workload["questions_a_document"]))
    built = build(run, model)
    with run.clock.phase("prompts"):
        pool = model.prompts(cfg, workload, run.seed)
        documents = model.documents(cfg, workload, run.seed)
    with run.clock.phase("session"):
        init, inputs = make_session(run, model, documents)
    generate = serve(run, model, init, built)
    del built
    before = shared.trace_lower_seconds()
    with run.clock.phase("warmup"):
        generate(pool[0], gen_len)
    setup = run.compiles.snapshot()
    run.facts.update(setup_compile_s=setup["seconds"],
                     setup_cache_misses=setup["misses"],
                     decode_trace_lower_s=shared.trace_lower_seconds()
                     - before)

    run.start_window()
    calls, (start, end) = share.window(run, generate, pool, run.seconds, 1)
    compiled = run.compiles.since(setup)["compiles"]
    tokens = sum(call[1].size for call in calls)
    rate = tokens / (end - start) / len(run.devices)
    facts = run.facts
    facts.update(
        long_calls=len(calls),
        long_call_ms=(end - start) / len(calls) * 1e3,
        long_batch=workload["batch"], long_session_len=session_len,
        long_prompt_len=prompt_len, long_gen_len=gen_len,
        compiles_in_window=compiled)
    print("window: %d calls in %.3f s, %.1f ms a call, %.2f tok/s per chip"
          % (len(calls), end - start, facts["long_call_ms"], rate),
          flush=True)

    if run.trace:
        before = run.compiles.snapshot()
        with run.tracing():
            traced, (t0, t1) = share.window(run, generate, pool, 0.0,
                                            1 + len(calls))
        calls += traced
        facts["compiles_in_window"] += \
            run.compiles.since(before)["compiles"]
        facts.update(long_traced_call_ms=(t1 - t0) * 1e3,
                     long_step_applications=prompt_len + gen_len - 1)
        # prefill alone: a call that returns after the question's first
        # continuation; a program of its own, so one call to load it
        generate(pool[0], 1)
        t0 = time.perf_counter()
        with run.span("bench/prefill_only"):
            generate(pool[1 % len(pool)], 1)
        facts["long_prefill_ms"] = (time.perf_counter() - t0) * 1e3
        # the caches' way to the device alone, as every call pays it
        # first: the session's arrays put there once more
        t0 = time.perf_counter()
        jax.block_until_ready([jax.device_put(v) for v in init.values()])
        facts["long_restore_ms"] = (time.perf_counter() - t0) * 1e3
        print("traced call %.1f ms (tracing costs %+.2f%% a call), "
              "the question's prefill alone %.1f ms, the session's way to "
              "the device %.1f ms of it"
              % (facts["long_traced_call_ms"],
                 (facts["long_traced_call_ms"] / facts["long_call_ms"]
                  - 1) * 100, facts["long_prefill_ms"],
                 facts["long_restore_ms"]), flush=True)

    facts["memory_peak_bytes"] = harness.memory_peak_bytes(run.devices)
    print("memory: %s" % {k: v for k, v in
                          (run.devices[0].memory_stats() or {}).items()
                          if "peak" in k or "limit" in k}, flush=True)
    del generate, init
    checks = check(run, model, documents, pool, calls, inputs)
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
