"""A generation cell over one chip of a *decode pool* whose every layer
attends a chosen set of a grouped key/value cache: every row of a call
starts with a long session already in its caches, three a layer (keys
and values of every key/value head over the whole session, and the
chooser's keys), takes a further turn (a question through the step's own
prefill scan) and decodes a long answer,
`decoder.greedy(prompt=<[batch, prompt_len] ids>, max_len=gen_len,
init_state=<the session's caches, its position and its rope_delta>)` in
a closed loop, one call in flight, as drivers/decode_session.py drives
the sparse latent cell.  `window` and `checked_rows` are
drivers/decode_share.py's, `seeded` drivers/decode_session.py's and
`model_key`, `make_weights` and `trace_lower_seconds`
drivers/decode_program.py's, imported as they are (read those files for
the window and the rate).

What differs.  The documents hold images: set-up makes the session with
the cell's plain reference (benchmark/reference/keye_vl2.py: float32,
rounded once to the caches' types; rows that ask of one document share
its session) over the seeded tokens, the seeded vectors a vision tower
would have handed over at the image spans, and their three-part
positions (benchmark/models/keye_decode.py `images`), before the served
weights are on the device.  After an image of h x w tokens the rotary
position has advanced by max(h, w), not h * w, so the call is also
handed `rope_delta`, what a row's position differs from its slot by,
which the step hands on unchanged; the question and the answer are text.
Every call is handed the session's caches as *device arrays*, put on the
chip once in set-up (`sparse_restore_ms`, timed there), which
`ProgramDecoder` takes where they lie: handed over as host arrays at
every call, as the two sibling cells hand theirs, 5.7 GB took 4.2-5.9 s
of an 11 s call with the chip idle, by how busy the shared host was, and
six seeds spread 3.2% where a cell is admitted under 0.5% (PERF.md
section 6, PR 58).  A decode pool's caches arrive in device memory; the
way there is the prefill pool's and the fabric's, not this chip's step.
The facts have names of their own (`sparse_*`), so that the readers
written for the other generation cells find nothing to read here.

`correct`, after the window, over the checked rows of one call, is
decode_session.py's: the served tokens against the reference continued
from the session's own forward (`gap_mean`, `not_first_share`); and of
the call's last step, carried out of the decoder as state pairs the step
only writes, each layer judged on the input the program itself gave it:
`selected_share` (a floor), `attn_off`, `attn_off_first`,
`held_part_off`.  The first layer's caches differ from the reference's by
their rounding alone, so `attn_off_first` is what says a cache in a
narrower type, a query turned at its slot and not its position, or a
session whose images were laid out otherwise apart from a sound run.

Two keys of the workload are controls of `correct` and absent from the
cell's file: `rope_delta_zero` (the call is handed a delta of 0: the slot
taken for the position) and `session_control` (the session is made by
the reference under that `control`, as a prefill pool that lays images
out otherwise would have made it); `index_topk` serves another `topk`.
"""

import gc
import time

import numpy as np

from benchmark import harness


def seeded(run, model):
    """drivers/decode_session.py's: (`ends`, `block_of(layer)`), the
    parameters `make_weights` serves as the reference asks for them."""
    return run.lookup.module("drivers", "decode_session").seeded(run, model)


def make_session(run, model, documents, seen):
    """({cache feed: the step's declared shape in the cache's type,
    "pos", "rope_delta": [batch]} on the host: what every call starts
    from; and what `compare` continues the reference from after the
    window: {document: every layer's float32 input over it}, for the
    documents the checked rows ask of, 0.53 GB a layer and document on
    the host, so that the reference runs once over a document and not
    again for `correct`).  `seen`: the model builder's `images`."""
    import jax.numpy as jnp

    cfg, workload = run.config, run.workload
    reference = run.lookup.module("reference", workload["reference"])
    share = run.lookup.module("drivers", "decode_share")
    ends, block_of = seeded(run, model)
    batch, each = workload["batch"], workload["questions_a_document"]
    made, inputs = reference.session(
        dict(cfg, control=workload.get("session_control", {})), ends,
        block_of, documents, seen["positions"], seen["vectors"],
        seen["slots"], workload["reference_query_block"],
        keep=set(share.checked_rows(run) // each))
    length, positions = documents.shape[1], cfg["serve_positions"]
    delta = 0 if workload.get("rope_delta_zero") else seen["rope_delta"]
    init = {"pos": np.full((batch,), length, np.int64),
            "rope_delta": np.full((batch,), delta, np.int64)}
    # float32 out of the reference; each cache rounds once, to its type
    for layer, (keys, values, index_keys) in enumerate(made):
        for name, value, dtype, axis in (
                ("k_cache_%d" % layer, keys, workload["serve_dtype"], 2),
                ("v_cache_%d" % layer, values, workload["serve_dtype"], 2),
                ("index_cache_%d" % layer, index_keys,
                 workload["index_dtype"], 1)):
            shape = list(value.shape)
            shape[0], shape[axis] = batch, positions
            cache = np.zeros(shape, jnp.dtype(dtype))
            cache[(slice(None),) * axis + (slice(0, length),)] = np.repeat(
                value, each, axis=0).astype(cache.dtype)
            init[name] = cache
    return init, inputs


def build(run, model):
    """The builder's step at the cell's batch.  First in a run, before
    the minute the session takes: a program that cannot build this step
    (the parent commit's) fails here, at once."""
    cfg, workload = run.config, run.workload
    changed = {}
    if "index_topk" in workload:    # a control of `correct`
        sa = cfg["sa_config"]
        changed["indexer"] = (sa["indexer_num_heads"],
                              sa["indexer_head_dim"], workload["index_topk"])
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
    for feed, shape in built["cache_shapes"].items():
        if init[feed].shape != shape:
            raise ValueError("the session's %r is %s, the program's %s"
                             % (feed, init[feed].shape, shape))
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
    # the session, on the device once: every call starts from these
    # arrays and writes to none of them
    t0 = time.perf_counter()
    init = {feed: jax.device_put(value) for feed, value in init.items()}
    jax.block_until_ready(list(init.values()))
    run.facts["sparse_restore_ms"] = (time.perf_counter() - t0) * 1e3
    print("setup restore      %8.3f s" % (run.facts["sparse_restore_ms"]
                                          / 1e3), flush=True)
    # a probe starts as zeros of what the step writes there: activations
    # in the weights' type, choices as int32
    row = np.zeros((batch, 1, cfg["hidden_size"]),
                   jnp.dtype(workload["weights"]["dtype"]))
    top_k = workload.get("index_topk", cfg["sa_config"]["topk"])
    ints = {"selected": (batch, top_k),
            "idx": (batch, cfg["num_experts_per_tok"])}
    for _, pairs in built["probes"]:
        init.update({feed: np.zeros(ints[what], np.int32) if what in ints
                     else row for what, (feed, _) in pairs.items()})
    eos = cfg["vocab_size"]     # outside the vocabulary: no early stop

    def generate(prompt, max_len):
        tokens, lengths, last = decoder.greedy(
            bos=0, eos=eos, max_len=max_len, init_state=init,
            prompt=prompt, return_state=sorted(probes))
        return tokens, lengths, {
            layer: {what: last[feed] for what, (feed, _) in pairs.items()}
            for layer, pairs in built["probes"]}

    return generate


def compare(run, model, documents, seen, pool, call, inputs):
    """What `correct` can rest on (`inputs`: `make_session`'s second): over the checked rows of one call,
    the mean gap by which a served token's reference logit lies below
    the reference's best and the share of served tokens that are not the
    reference's first; and of the call's last step, each layer on the
    program's own input to it: the smallest share a layer's chooser
    picked of the set the reference would choose (`selected_share`), the
    largest distance of a layer's attention output from the reference's
    over the program's set, as the root mean square of the difference
    over the reference's (`attn_off`; `attn_off_first` is the first
    layer's, whose caches hold no drift of the call's own), both over the
    checked rows, and over every row `held_part_off`."""
    import jax

    gc.collect()    # the decoder the caller has let go of, caches and all
    cfg, workload = run.config, run.workload
    share = run.lookup.module("drivers", "decode_share")
    reference = run.lookup.module("reference", workload["reference"])
    ends, block_of = seeded(run, model)
    index, tokens, _, probes = call
    rows = share.checked_rows(run)
    each = workload["questions_a_document"]
    whole = np.concatenate([documents[rows // each], pool[index][rows],
                            tokens[rows]], axis=1)
    # the turn is text: its positions go on from where the document's end
    turn = documents.shape[1] + seen["rope_delta"] \
        + np.arange(whole.shape[1] - documents.shape[1])
    positions = np.concatenate(
        [seen["positions"][:, rows // each],
         np.broadcast_to(turn, (3, rows.size, turn.size))], axis=2)
    # the call's last step read the token before the last served one,
    # at the position before the last
    at = whole.shape[1] - 2
    layers = sorted(probes)
    last = {"at": at, "live": min(probes[layers[0]]["selected"].shape[1],
                                  at + 1),
            "attn_in": [probes[k]["attn_in"][rows][:, 0] for k in layers],
            "selected": [probes[k]["selected"][rows] for k in layers]}
    off = {}

    def held_part(layer, block):
        if "idx" in probes[layer]:
            off[layer] = reference.held_part_off(cfg, block, probes[layer])

    found, step = reference.gaps(
        cfg, ends, block_of, whole, positions,
        documents.shape[1] + pool.shape[2] - 1, tokens[rows],
        workload["reference_query_block"], last, held_part,
        [inputs[int(d)] for d in rows // each])
    gaps = np.asarray(jax.device_get(found)).astype(np.float64)
    attn_off = []
    for k, want in zip(layers, step["attn"]):
        want = np.asarray(want, np.float64)
        got = np.asarray(probes[k]["attn_out"][rows][:, 0], np.float64)
        attn_off.append(float(np.sqrt(np.mean(np.square(got - want))
                                      / np.mean(np.square(want)))))
    shared = [float(np.min(s)) for s in step["shared"]]
    # "distinct" is not compared: how varied the served text is
    return {"gap_max": float(gaps.max()), "gap_mean": float(gaps.mean()),
            "not_first_share": float((gaps > 0).mean()),
            "selected_share": min(shared),
            "selected_share_by_layer": shared,
            "attn_off": max(attn_off), "attn_off_first": attn_off[0],
            "attn_off_by_layer": attn_off,
            "held_part_off": max(off.values()),
            "held_part_off_by_layer": [off[k] for k in sorted(off)],
            "tokens": int(gaps.size), "rows": int(rows.size),
            "distinct": int(np.unique(tokens).size)}


FLOORS = ("selected_share",)    # every other limit is a ceiling


def check(run, model, documents, seen, pool, calls, inputs):
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
            got = compare(run, model, documents, seen, pool, calls[picked],
                          inputs)
        print("call %d: %d tokens of %d rows, %d distinct in the call, "
              "%.4f%% not the reference's first; of the last step, by "
              "layer: the chooser picked %s of the reference's own set, "
              "the attention over its set is off by %s, the held experts' "
              "part by %s"
              % (picked, got["tokens"], got["rows"], got["distinct"],
                 100 * got["not_first_share"],
                 ", ".join("%.4f" % v
                           for v in got["selected_share_by_layer"]),
                 ", ".join("%.5f" % v for v in got["attn_off_by_layer"]),
                 ", ".join("%.5f" % v
                           for v in got["held_part_off_by_layer"])),
              flush=True)
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

    import jax

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
        seen = model.images(cfg, workload, run.seed)
    with run.clock.phase("session"):
        init, inputs = make_session(run, model, documents, seen)
    run.facts["sparse_cache_bytes"] = sum(
        v.nbytes for k, v in init.items() if "cache" in k)
    print("session as handed in: %.4f GB of caches, rope_delta %d"
          % (run.facts["sparse_cache_bytes"] / 1e9,
             int(init["rope_delta"][0])), flush=True)
    generate = serve(run, model, init, built)
    del built, init     # the host's copy of the session: 5.7 GB
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
        sparse_calls=len(calls),
        sparse_call_ms=(end - start) / len(calls) * 1e3,
        sparse_batch=workload["batch"], sparse_session_len=session_len,
        sparse_prompt_len=prompt_len, sparse_gen_len=gen_len,
        compiles_in_window=compiled)
    print("window: %d calls in %.3f s, %.1f ms a call, %.2f tok/s per chip"
          % (len(calls), end - start, facts["sparse_call_ms"], rate),
          flush=True)

    if run.trace:
        before = run.compiles.snapshot()
        with run.tracing():
            traced, (t0, t1) = share.window(run, generate, pool, 0.0,
                                            1 + len(calls))
        calls += traced
        facts["compiles_in_window"] += \
            run.compiles.since(before)["compiles"]
        facts.update(sparse_traced_call_ms=(t1 - t0) * 1e3,
                     sparse_step_applications=prompt_len + gen_len - 1)
        # prefill alone: a call that returns after the question's first
        # continuation; a program of its own, so one call to load it
        generate(pool[0], 1)
        t0 = time.perf_counter()
        with run.span("bench/prefill_only"):
            generate(pool[1 % len(pool)], 1)
        facts["sparse_prefill_ms"] = (time.perf_counter() - t0) * 1e3
        print("traced call %.1f ms (tracing costs %+.2f%% a call), "
              "the question's prefill alone %.1f ms; the session's way to "
              "the device, once in set-up, %.1f ms"
              % (facts["sparse_traced_call_ms"],
                 (facts["sparse_traced_call_ms"] / facts["sparse_call_ms"]
                  - 1) * 100, facts["sparse_prefill_ms"],
                 facts["sparse_restore_ms"]), flush=True)

    facts["memory_peak_bytes"] = harness.memory_peak_bytes(run.devices)
    print("memory: %s" % {k: v for k, v in
                          (run.devices[0].memory_stats() or {}).items()
                          if "peak" in k or "limit" in k}, flush=True)
    del generate
    checks = check(run, model, documents, seen, pool, calls, inputs)
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
