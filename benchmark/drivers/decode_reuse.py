"""A generation cell over one chip of a *decode pool* whose layers do not
all choose for themselves: every row of a call starts with a long session
already in its caches (latents a layer; the chooser's keys on the layers
`indexer_types` calls full and on no other), takes a further turn (a
question through the step's own prefill scan) and decodes a long answer,
`decoder.greedy(prompt=<[batch, prompt_len] ids>, max_len=gen_len,
init_state=<the session's caches and its position>)` in a closed loop,
one call in flight, as drivers/decode_session.py drives the sparse
latent cell.  `window` and `checked_rows` are drivers/decode_share.py's,
`seeded` drivers/decode_session.py's and `model_key`, `make_weights` and
`trace_lower_seconds` drivers/decode_program.py's, imported as they are
(read those files for the window and the rate).

What differs from decode_session.py, which makes an `index_cache_<i>` for
every layer.  The step's state is what the built step declares
(`cache_shapes`): a layer that inherits its set has no index cache, and
the session the reference makes has none for it.  The residual is four
streams of float32 a token in the reference (3.1 GB a document and
layer), so the reference keeps no layer's input over a document: after
the window it continues from the session's own float32 cache entries,
which are all a layer reads of the positions before the turn
(benchmark/reference/hy4_preview.py `gaps`).  Every call is handed the
session's caches as *device arrays*, put on the chip once in set-up
(`reuse_restore_ms`, timed there), as drivers/decode_sparse.py hands
them and for its reason: a decode pool's caches arrive in device memory,
and the way there from this host is the shared host's, not the step's.
The facts have names of their own (`reuse_*`), so that the readers
written for the other generation cells find nothing to read here:
`dsa_index_roofline` counts index scores on every layer, which here
would be 2.5 times the work.

`correct`, after the window, over the checked rows of one call, is
decode_session.py's (`gap_mean`, `not_first_share`; of the call's last
step, each layer judged on the input the program itself gave it:
`selected_share` over the layers that choose, `attn_off`,
`attn_off_first`, `held_part_off`) and two numbers more: `shared_off`,
the attention sub-layer's output of the layers that do not choose against
the reference attending **the upstream layer's set** as the program
carried it out (the largest of them), and `mix_off`, the streams a
layer's attention hyper-connection wrote against the reference's for the
program's own streams and sub-layer output (the largest of the layers').

One key of the workload is a control of `correct` and absent from the
cell's file: `control` = {"gated": false | "sink": false |
"hc_iterations": 1}, the step built without the gate, without the sink or
with so many Sinkhorn iterations, held to the reference of the
configuration as stated (benchmark/tests/reuse_control.py).
"""

import gc
import time

import numpy as np

from benchmark import harness


def seeded(run, model):
    """drivers/decode_session.py's: (`ends`, `block_of(layer)`), the
    parameters `make_weights` serves as the reference asks for them."""
    return run.lookup.module("drivers", "decode_session").seeded(run, model)


def make_session(run, model, documents, made=None):
    """({cache feed: the step's declared shape in the cache's type, "pos":
    [batch]} on the host: what every call starts from; and what `compare`
    continues the reference from after the window: [layers] x (latents
    [documents, session, .], index keys or None) in float32, 0.09 GB a
    layer and document on the host).  `made`: that second result of an
    earlier call for the same documents and model, to round again (a
    control that serves another cache type) without the reference's
    minutes."""
    import jax.numpy as jnp

    cfg, workload = run.config, run.workload
    batch, each = workload["batch"], workload["questions_a_document"]
    if made is None:
        reference = run.lookup.module("reference", workload["reference"])
        ends, block_of = seeded(run, model)
        made = reference.session(
            cfg, ends, block_of, documents,
            workload["reference_query_block"],
            workload["reference_head_groups"])
    length, positions = documents.shape[1], cfg["serve_positions"]
    init = {"pos": np.full((batch,), length, np.int64)}
    # float32 out of the reference; each cache rounds once, to its type
    for layer, (latents, keys) in enumerate(made):
        for name, value, dtype in (
                ("latent_cache_%d" % layer, latents, workload["serve_dtype"]),
                ("index_cache_%d" % layer, keys, workload["index_dtype"])):
            if value is None:   # a layer that inherits its set
                continue
            cache = np.zeros((batch, positions, value.shape[-1]),
                             jnp.dtype(dtype))
            cache[:, :length] = np.repeat(value, each, axis=0).astype(
                cache.dtype)
            init[name] = cache
    return init, made


def build(run, model):
    """The builder's step at the cell's batch.  First in a run, before
    the minutes the session takes: a program that cannot build this step
    (the parent commit's) fails here, at once."""
    cfg, workload = run.config, run.workload
    control, changed = workload.get("control", {}), {}
    for key in ("gated", "sink"):
        if key in control:
            changed[key] = control[key]
    if "hc_iterations" in control:
        changed["hc"] = dict(model.sizes(cfg)["hc"],
                             iterations=control["hc_iterations"])
    with run.clock.phase("build"):
        return model.build(cfg, workload["batch"], **changed)


def serve(run, model, init, built):
    """`generate(prompt, max_len) -> (tokens, lengths, probes)` on the
    host: `build`'s step Program at the cell's batch, the seeded weights
    in a scope under the program's names, a `ProgramDecoder` over them,
    and the session every call starts from, on the device."""
    import jax
    import jax.numpy as jnp
    import paddle_tpu.fluid as fluid

    cfg, workload = run.config, run.workload
    shared = run.lookup.module("drivers", "decode_program")
    if set(built["cache_shapes"]) != set(init) - {"pos"}:
        raise ValueError("the session holds %s, the program's step %s"
                         % (sorted(init), sorted(built["cache_shapes"])))
    for feed, shape in built["cache_shapes"].items():
        if init[feed].shape != shape:
            raise ValueError("the session's %r is %s, the program's %s"
                             % (feed, init[feed].shape, shape))
    scope = fluid.Scope()
    with run.clock.phase("weights"):
        made = shared.make_weights(run, model)
        block = built["main"].global_block()
        names = built["param_names"]
        # by name, not in the trees' order: a control's step names fewer
        # parameters than the model draws
        pairs = [(names[k], made[k]) for k in ("embed", "norm_f", "head")]
        for named, drawn in zip(names["blocks"], made["blocks"]):
            pairs += [(name, drawn[what]) for what, name in named.items()]
        for name, value in pairs:
            declared = tuple(block.var(name).shape)
            if declared != value.shape:
                raise ValueError("the program's %r is %s, the seeded "
                                 "weight %s" % (name, declared, value.shape))
            scope.set(name, value)
        run.facts["reuse_parameters"] = sum(v.size for _, v in pairs)
        run.facts["reuse_parameter_bytes"] = sum(v.nbytes for _, v in pairs)
        del made, pairs
    probes = {feed: out for _, found in built["probes"]
              for feed, out in found.values()}
    with run.clock.phase("decoder"):
        decoder = fluid.ProgramDecoder(
            built["main"].clone(for_test=True), token_name="tok",
            logits_name=built["logits"].name,
            state_pairs=built["state_pairs"] + list(probes.items()),
            scope=scope, max_positions=cfg["serve_positions"])
    del scope
    # the session, on the device once: every call starts from these
    # arrays and writes to none of them
    t0 = time.perf_counter()
    init = {feed: jax.device_put(value) for feed, value in init.items()}
    jax.block_until_ready(list(init.values()))
    run.facts["reuse_restore_ms"] = (time.perf_counter() - t0) * 1e3
    print("setup restore      %8.3f s" % (run.facts["reuse_restore_ms"]
                                          / 1e3), flush=True)
    batch, hidden = workload["batch"], cfg["hidden_size"]
    # a probe starts as zeros of what the step writes there: activations
    # in the weights' type, choices as int32
    dtype = jnp.dtype(workload["weights"]["dtype"])
    zeros = {"selected": np.zeros((batch, cfg["index_topk"]), np.int32),
             "idx": np.zeros((batch, cfg["num_experts_per_tok"]), np.int32)}
    row = np.zeros((batch, 1, hidden), dtype)
    streams = np.zeros((batch, 1, cfg["hc_mult"], hidden), dtype)
    for _, found in built["probes"]:
        init.update({feed: zeros.get(what, streams if what.startswith(
            "streams") else row) for what, (feed, _) in found.items()})
    eos = cfg["vocab_size"]     # outside the vocabulary: no early stop

    def generate(prompt, max_len):
        tokens, lengths, last = decoder.greedy(
            bos=0, eos=eos, max_len=max_len, init_state=init,
            prompt=prompt, return_state=sorted(probes))
        return tokens, lengths, {
            layer: {what: last[feed] for what, (feed, _) in found.items()}
            for layer, found in built["probes"]}

    return generate


def _off(got, want):
    """Root mean square of the difference over the reference's."""
    got, want = (np.asarray(a, np.float64) for a in (got, want))
    return float(np.sqrt(np.mean(np.square(got - want))
                         / np.mean(np.square(want))))


def compare(run, model, documents, pool, call, session):
    """What `correct` can rest on (`session`: `make_session`'s second):
    over the checked rows of one call, the mean gap by which a served
    token's reference logit lies below the reference's best and the share
    of served tokens that are not the reference's first; and of the
    call's last step, each layer on the program's own input to it: the
    smallest share a choosing layer's chooser picked of the set the
    reference would choose (`selected_share`), the largest distance of a
    layer's attention output from the reference's over the set the
    program attended (`attn_off`; `attn_off_first` the first layer's,
    `shared_off` the largest of the layers that inherit their set) and of
    the streams its hyper-connection wrote from the reference's
    (`mix_off`), over the checked rows, and over every row
    `held_part_off`."""
    import jax

    gc.collect()    # the decoder the caller has let go of, caches and all
    cfg, workload = run.config, run.workload
    share = run.lookup.module("drivers", "decode_share")
    reference = run.lookup.module("reference", workload["reference"])
    ends, block_of = seeded(run, model)
    index, tokens, _, probes = call
    rows = share.checked_rows(run)
    each = workload["questions_a_document"]
    turn = np.concatenate([pool[index][rows], tokens[rows]], axis=1)
    # the call's last step read the token before the last served one, at
    # the position before the last
    at = documents.shape[1] + turn.shape[1] - 2
    layers = sorted(probes)

    def of(what, squeeze=True):
        return [np.asarray(probes[k][what])[rows][:, 0] if squeeze
                else np.asarray(probes[k][what])[rows] for k in layers]

    last = {"at": at, "live": min(cfg["index_topk"], at + 1),
            "attn_in": of("attn_in"), "selected": of("selected", False),
            "streams_in": of("streams_in"), "attn_out": of("attn_out")}
    off = {}

    def held_part(layer, block):
        if "idx" in probes[layer]:
            off[layer] = reference.held_part_off(cfg, block, probes[layer])

    prefixes = [[tuple(None if a is None else a[int(d)] for a in made)
                 for made in session] for d in rows // each]
    found, step = reference.gaps(
        cfg, ends, block_of, turn, prefixes, tokens[rows],
        workload["reference_query_block"], last, held_part,
        workload["reference_head_groups"])
    gaps = np.asarray(jax.device_get(found)).astype(np.float64)
    attn_off = [_off(got, want)
                for got, want in zip(last["attn_out"], step["attn"])]
    mix_off = [_off(got, want)
               for got, want in zip(of("streams_out"), step["streams"])]
    chooses = [s[0] is not None for s in step["shared"]]
    picked = [float(np.min(s)) for s, own in zip(step["shared"], chooses)
              if own]
    inherited = [a for a, own in zip(attn_off, chooses) if not own]
    # "distinct" is not compared: how varied the served text is
    return {"gap_max": float(gaps.max()), "gap_mean": float(gaps.mean()),
            "not_first_share": float((gaps > 0).mean()),
            "selected_share": min(picked),
            "selected_share_by_layer": picked,
            "attn_off": max(attn_off), "attn_off_first": attn_off[0],
            "shared_off": max(inherited), "attn_off_by_layer": attn_off,
            "mix_off": max(mix_off), "mix_off_by_layer": mix_off,
            "held_part_off": max(off.values()),
            "held_part_off_by_layer": [off[k] for k in sorted(off)],
            "tokens": int(gaps.size), "rows": int(rows.size),
            "distinct": int(np.unique(tokens).size)}


FLOORS = ("selected_share",)    # every other limit is a ceiling


def check(run, model, documents, pool, calls, session):
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
                          session)

        def listed(key, form="%.5f"):
            return ", ".join(form % v for v in got[key])

        print("call %d: %d tokens of %d rows, %d distinct in the call, "
              "%.4f%% not the reference's first; of the last step, by "
              "layer: the choosers picked %s of the reference's own sets, "
              "the attention over the program's set is off by %s, the "
              "streams its hyper-connection wrote by %s, the held experts' "
              "part by %s"
              % (picked, got["tokens"], got["rows"], got["distinct"],
                 100 * got["not_first_share"],
                 listed("selected_share_by_layer", "%.4f"),
                 listed("attn_off_by_layer"), listed("mix_off_by_layer"),
                 listed("held_part_off_by_layer")), flush=True)
        for name in sorted(set(limits) - {"why"}):
            floor = name in FLOORS
            checks["%s %.6g over %d rows of call %d, %s %.6g"
                   % (name, got[name], got["rows"], picked,
                      "at least" if floor else "limit", limits[name])] = \
                got[name] >= limits[name] if floor \
                else got[name] <= limits[name]
    return checks


def run(run):
    import sys

    cfg, workload = run.config, run.workload
    model = run.lookup.module("models", workload["builder"])
    share = run.lookup.module("drivers", "decode_share")
    shared = run.lookup.module("drivers", "decode_program")
    gen_len, prompt_len = workload["gen_len"], workload["prompt_len"]
    session_len = workload["session_len"]
    # what `ProgramDecoder._check_extent` cannot see: the position the
    # call starts from lies inside init_state
    if session_len + prompt_len + gen_len - 1 > cfg["serve_positions"] \
            or workload["batch"] != workload["documents"] \
            * workload["questions_a_document"]:
        raise SystemExit(
            "benchmark: a session of %d positions, a prompt of %d and %d "
            "generated tokens do not fit %d cache positions, or %d rows "
            "are not %d documents x %d questions"
            % (session_len, prompt_len, gen_len, cfg["serve_positions"],
               workload["batch"], workload["documents"],
               workload["questions_a_document"]))
    built = build(run, model)
    with run.clock.phase("prompts"):
        pool = model.prompts(cfg, workload, run.seed)
        documents = model.documents(cfg, workload, run.seed)
    with run.clock.phase("session"):
        init, session = make_session(run, model, documents)
    run.facts["reuse_cache_bytes"] = sum(
        v.nbytes for k, v in init.items() if "cache" in k)
    print("session as handed in: %.4f GB of caches (%s)"
          % (run.facts["reuse_cache_bytes"] / 1e9,
             ", ".join(sorted(k for k in init if "cache" in k))), flush=True)
    generate = serve(run, model, init, built)
    print("the step holds %.4f G parameters, %.4f GB as served"
          % (run.facts["reuse_parameters"] / 1e9,
             run.facts["reuse_parameter_bytes"] / 1e9), flush=True)
    del built, init     # the host's copy of the session
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
        reuse_calls=len(calls),
        reuse_call_ms=(end - start) / len(calls) * 1e3,
        reuse_batch=workload["batch"], reuse_session_len=session_len,
        reuse_prompt_len=prompt_len, reuse_gen_len=gen_len,
        compiles_in_window=compiled)
    print("window: %d calls in %.3f s, %.1f ms a call, %.2f tok/s per chip"
          % (len(calls), end - start, facts["reuse_call_ms"], rate),
          flush=True)

    if run.trace:
        before = run.compiles.snapshot()
        with run.tracing():
            traced, (t0, t1) = share.window(run, generate, pool, 0.0,
                                            1 + len(calls))
        calls += traced
        facts["compiles_in_window"] += \
            run.compiles.since(before)["compiles"]
        facts.update(reuse_traced_call_ms=(t1 - t0) * 1e3,
                     reuse_step_applications=prompt_len + gen_len - 1)
        print("traced call %.1f ms (tracing costs %+.2f%% a call); the "
              "session's way to the device, once in set-up, %.1f ms"
              % (facts["reuse_traced_call_ms"],
                 (facts["reuse_traced_call_ms"] / facts["reuse_call_ms"]
                  - 1) * 100, facts["reuse_restore_ms"]), flush=True)

    facts["memory_peak_bytes"] = harness.memory_peak_bytes(run.devices)
    print("memory: %s" % {k: v for k, v in
                          (run.devices[0].memory_stats() or {}).items()
                          if "peak" in k or "limit" in k}, flush=True)
    del generate
    checks = check(run, model, documents, pool, calls, session)
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
