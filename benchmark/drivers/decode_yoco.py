"""A generation cell over a chip that serves a decoder-hybrid-decoder
*whole*: every row of a call starts with a long session already in its
states, of four kinds in one step (a Mamba layer's scan state and
convolution tail, a window layer's last `sliding_window` positions in a
ring, and the one full layer's keys and values over the whole session,
which seven cross layers read and none of them writes), takes a further
turn (a question through the step's own block prefill, whose
cross-decoder runs at each block's last position alone) and decodes an
answer, `decoder.greedy(prompt=<[batch, prompt_len] ids>,
max_len=gen_len, init_state=<the session's states and its position>)`
in a closed loop, one call in flight, as drivers/decode_long.py drives
the window cell.  `window` and `checked_rows` are
drivers/decode_share.py's, `seeded` drivers/decode_session.py's and
`model_key`, `make_weights` and `trace_lower_seconds`
drivers/decode_program.py's, imported as they are (read those files for
the window and the rate).

What differs.  Set-up makes the session with the cell's plain reference
(benchmark/reference/phi4_flash.py: float32, a document once, its
self-decoder alone since no layer past the full one holds a state; rows
that ask of one document share it), before the served weights are on the
device, lays it out as the step's feeds hold it (`lay_out`: a pair's two
key heads side by side, a ring's position p in slot p mod window, the
scan's state entries by channels) and rounds it once to the feeds' types
(keys and values `serve_dtype`, scan states `state_dtype`, tails the
weights' type); every
call is handed it as *device arrays*, put on the chip once
(drivers/decode_sparse.py says why).  The facts have names of their own
(`yoco_*`), so that the readers written for the other generation cells
find nothing to read here.

`correct`, after the window, over the checked rows of one call: the
served tokens against the reference continued from the session's own
float32 states over question and answer, every layer at every position
(`gap_mean`, `not_first_share`, as the share cells'); and of the call's
last step, carried out of the decoder as state pairs the step only
writes, each sub-layer judged on the input the program itself gave it,
as the root mean square of the difference over the reference's, the
worst of its kind: `attn_off_window`, `attn_off_full` and
`attn_off_cross` (the attention layers' outputs; a cross layer against
the reference's own keys and values of layer 17), `ssm_off` (a Mamba
layer's scan output before the gate against the reference's of the
convolved input the scan read and the state it left, both the
program's own; and that state against the reference's own after that
position: the larger) and `gmu_off` (a memory unit's output against the
reference's over the program's own memory of that step, layer 16's scan
output).  What a layer holds of the positions before, the reference
holds of its own: a ring, a cache and a scan's state hold many positions
and the readings over them are steady, though a layer deep in the
self-decoder holds what the program computed from inputs that have
drifted from the reference's by every rounding upstream, and its reading
holds that too.  What is *one* position's is the program's own, the
convolved input a scan read and the memory a unit read: judged against
the reference's own of that one position (three positions of its tail,
sixteen entries of `C` that every channel shares) a sound run reads
0.06-0.10 and now and then twice that, since one position's drift is
not steady (the workload file's `correct.why`).  Layer 0's input is
the tokens' embedding, the same on both sides, so `ssm_off_first`
(layer 0's scan alone) is what says a state carried in a narrower type
apart from a sound run, and `attn_off_first` (layer 1's ring) a ring
written wrongly, as exaone's.  One slot among sixteen
thousand moves a cross layer's output by less than its rounding, so
`attn_off_cross` cannot say whether the layer read the cache as the step
wrote it.  `compare` also gives `own_slot_share`, which no run's
`correct` rests on: the part of what the slot of the step's own position
adds to the reference's output (the reference with that slot, less the
reference without it) that the program's output carries, along that
direction, over the cross layers and the checked rows together (the
least-squares share): near 1 where the slot was read and near 0 where it
was missed, but with the rounding's part along that one direction on
top, which at 16,000 slots is a few tenths (the workload file's
`correct.why` has the readings); benchmark/tests/yoco_control.py prints
it, and from empty states scripts/phi4flash_check.py refuses the wiring
outright.

Keys of the workload that are controls of `correct` and absent from, or
at their sound values in, the cell's file: `window` (a narrower ring),
`serve_dtype` and `state_dtype` (narrower caches, a narrower carried
state), and `control.subtract`, `control.memory_after_gate`,
`control.cross_before_write` (the builder's three wrong wirings).
"""

import gc
import time

import numpy as np

from benchmark import harness

MAMBA, WINDOW, FULL, GMU, CROSS = "mamba", "window", "full", "gmu", "cross"
OFF = {WINDOW: "attn_off_window", FULL: "attn_off_full",
       CROSS: "attn_off_cross", MAMBA: "ssm_off", GMU: "gmu_off"}


def seeded(run, model):
    """drivers/decode_session.py's: (`ends`, `block_of(layer)`), the
    parameters `make_weights` serves as the reference asks for them."""
    return run.lookup.module("drivers", "decode_session").seeded(run, model)


def reference_session(run, model, documents):
    """(the reference's float32 states of every document, as
    `reference.session` gives them; and what `compare` continues the
    reference from after the window: the reference's compiled layers and
    the same states of the documents the checked rows ask of)."""
    cfg, workload = run.config, run.workload
    reference = run.lookup.module("reference", workload["reference"])
    share = run.lookup.module("drivers", "decode_share")
    ends, block_of = seeded(run, model)
    layers = reference.Layers(cfg, workload["reference_query_block"])
    made, kept = reference.session(
        cfg, layers, ends, block_of, documents, workload["reference_turn"],
        cfg["serve_positions"],
        keep=set(share.checked_rows(run) // workload["questions_a_document"]))
    return made, (layers, kept)


def lay_out(run, model, made, length):
    """{state feed: the step's declared shape in the feed's type, "pos":
    [batch]} on the host, from `reference_session`'s first: what every
    call starts from."""
    import jax.numpy as jnp

    cfg, workload = run.config, run.workload
    batch, each = workload["batch"], workload["questions_a_document"]
    kinds = model.kinds(cfg)
    serve = jnp.dtype(workload["serve_dtype"])
    tails = jnp.dtype(workload["weights"]["dtype"])
    state = jnp.dtype(workload.get("state_dtype", "float32"))
    # a control serves a narrower ring: the last `window` positions
    window = workload.get("window", cfg["sliding_window"])
    init = {"pos": np.full((batch,), length, np.int64)}

    def rows(value, dtype):
        # float32 out of the reference; a state rounds once, to its type
        return np.repeat(value.astype(dtype), each, axis=0)

    for i, (first, second) in made.items():
        if kinds[i] == MAMBA:
            init["ssm_state_%d" % i] = rows(first.transpose(0, 2, 1), state)
            init["conv_tail_%d" % i] = rows(second, tails)
            continue
        stem = "%s_ring_%d" if kinds[i] == WINDOW else "%s_cache_%d"
        for which, value in zip("kv", (first, second)):
            # [documents, positions, kv heads, dim] -> a pair's two heads
            # side by side, [documents, pairs, positions, 2 * dim]
            docs, slots, heads, dim = value.shape
            value = value.reshape(docs, slots, heads // 2, 2 * dim) \
                .transpose(0, 2, 1, 3)
            if kinds[i] == WINDOW:
                # the reference holds the last positions in order;
                # position p lives in slot p mod window
                value = np.roll(value[:, :, slots - window:],
                                length % window, axis=2)
            init[stem % (which, i)] = rows(value, serve)
    return init


def make_session(run, model, documents):
    """(`lay_out`'s, `reference_session`'s second)."""
    made, inputs = reference_session(run, model, documents)
    return lay_out(run, model, made, documents.shape[1]), inputs


def build(run, model):
    """The builder's step at the cell's batch.  First in a run, before
    the session is made: a program that cannot build this step (the
    parent commit's) fails here, at once."""
    cfg, workload = run.config, run.workload
    changed = {key: value for key, value in
               workload.get("control", {}).items()
               if value != (key == "subtract")}
    if "window" in workload:
        changed["window"] = workload["window"]
    with run.clock.phase("build"):
        return model.build(cfg, workload["batch"], **changed)


def serve(run, model, init, built):
    """`generate(prompt, max_len) -> (tokens, lengths, probes)` on the
    host: `build`'s step Program at the cell's batch, the seeded weights
    in a scope under the program's names, a `ProgramDecoder` over them,
    and the session every call starts from, on the device once.
    `probes` is {layer: {"in", "out", and on a Mamba layer "xc" and
    "state", the scan state the call's last step left}}."""
    import jax
    import jax.numpy as jnp
    import paddle_tpu.fluid as fluid

    cfg, workload = run.config, run.workload
    shared = run.lookup.module("drivers", "decode_program")
    by_kind = {}
    for feed, shape in built["state_shapes"].items():
        if init[feed].shape != shape:
            raise ValueError("the session's %r is %s, the program's %s"
                             % (feed, init[feed].shape, shape))
        kind = feed.split("_")[1]   # state, tail, ring, cache
        by_kind[kind] = by_kind.get(kind, 0) + init[feed].nbytes
    run.facts["yoco_state_bytes"] = by_kind
    print("session as handed in: %s"
          % ", ".join("%s %.4f GB" % (kind, size / 1e9)
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
    # the session, on the device once: every call starts from these
    # arrays and writes to none of them
    t0 = time.perf_counter()
    init = {feed: jax.device_put(value) for feed, value in init.items()}
    jax.block_until_ready(list(init.values()))
    run.facts["yoco_restore_ms"] = (time.perf_counter() - t0) * 1e3
    print("setup restore      %8.3f s" % (run.facts["yoco_restore_ms"]
                                          / 1e3), flush=True)
    # a probe starts as zeros of what the step writes there:
    # activations in the weights' type
    dtype = jnp.dtype(workload["weights"]["dtype"])
    for feed, out in probes.items():
        init[feed] = np.zeros(
            (workload["batch"], 1, int(block.var(out).shape[-1])), dtype)
    scans = sorted(feed for feed in built["state_shapes"]
                   if feed.startswith("ssm_state_"))
    by_layer = built["probes"]  # the closure keeps this, not `built`
    eos = cfg["vocab_size"]     # outside the vocabulary: no early stop

    def generate(prompt, max_len):
        tokens, lengths, last = decoder.greedy(
            bos=0, eos=eos, max_len=max_len, init_state=init,
            prompt=prompt, return_state=sorted(probes) + scans)
        found = {layer: {what: last[feed]
                         for what, (feed, _) in pairs.items()}
                 for layer, pairs in by_layer}
        for feed in scans:
            found[int(feed.rsplit("_", 1)[1])]["state"] = last[feed]
        return tokens, lengths, found

    return generate


def _off(got, want):
    """The root mean square of the difference over the reference's."""
    got, want = (np.asarray(a, np.float64) for a in (got, want))
    return float(np.sqrt(np.mean(np.square(got - want))
                         / np.mean(np.square(want))))


def compare(run, model, documents, pool, call, inputs):
    """What `correct` can rest on (`inputs`: `make_session`'s second):
    over the checked rows of one call, the mean gap by which a served
    token's reference logit lies below the reference's best and the
    share of served tokens that are not the reference's first; and of
    the call's last step, each sub-layer on the program's own input to
    it, the worst of its kind (the module's docstring)."""
    import jax
    import jax.numpy as jnp

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
    kinds = model.kinds(cfg)
    # the call's last step read the token before the last served one,
    # at the position before the last
    last = {"at": start + turn.shape[1] - 2,
            "mixer_in": [probes[i]["in"][rows][:, 0]
                         for i in range(len(kinds))]}
    # the program's own memory of that step: layer half's scan output
    memory = probes[len(kinds) // 2]["out"][rows][:, 0]
    own = {}

    def on_its_own_input(i, block):
        """What the reference's float32 makes of everything the program
        itself gave layer i's sub-layer at that step: a memory unit of
        the program's memory, a scan's output of the convolved input it
        read and the state it left."""
        def f32(a):
            return jnp.asarray(np.asarray(a, np.float32))

        with jax.default_matmul_precision("highest"):
            if kinds[i] == GMU:
                own[i] = reference.memory_unit(
                    block, f32(last["mixer_in"][i]), f32(memory))
            elif kinds[i] == MAMBA:
                xc = f32(probes[i]["xc"][rows][:, 0])
                _, _, c = reference.steps(block, xc)
                own[i] = jnp.einsum("rnd,rn->rd",
                                    f32(probes[i]["state"][rows]), c) \
                    + block["d"] * xc

    found, want = reference.gaps(
        cfg, layers, ends, block_of, turn, start, pool.shape[2] - 1,
        tokens[rows], last, [kept[int(d)] for d in rows // each],
        with_block=on_its_own_input)
    gaps = np.asarray(jax.device_get(found)).astype(np.float64)
    by_layer, scan, own_slot = [], {"out": [], "state": []}, []
    along = moved_by = 0.0
    for i, kind in enumerate(kinds):
        got = probes[i]["out"][rows][:, 0]
        if kind == CROSS:
            fresh, stale = (np.stack([np.asarray(w[at], np.float64)
                                      for w in want[i]]) for at in (0, 1))
            by_layer.append(_off(got, fresh))
            # how much of what the slot of the step's own position adds
            # the program's output carries: 1 where the layer read the
            # cache as the step wrote it, 0 where as it stood before
            moved = fresh - stale
            part = np.sum((np.asarray(got, np.float64) - stale) * moved)
            own_slot.append(float(part / np.sum(np.square(moved))))
            along, moved_by = along + part, moved_by + np.sum(
                np.square(moved))
            continue
        if kind != MAMBA:
            by_layer.append(_off(got, own[i] if kind == GMU
                                 else np.stack(want[i])))
            continue
        out = _off(got, own[i])
        state = _off(
            np.asarray(probes[i]["state"][rows], np.float32)
            .transpose(0, 2, 1), np.stack([w[1] for w in want[i]]))
        scan["out"].append(out)
        scan["state"].append(state)
        by_layer.append(max(out, state))
    off = {name: max(v for v, k in zip(by_layer, kinds) if k == kind)
           for kind, name in OFF.items()}
    # the first layer of each stateful kind: its input is the tokens'
    # embedding (layer 0) or one layer from it (layer 1), the same on
    # both sides but for a layer's rounding, so what its state holds of
    # the call's own positions has not drifted
    off.update(ssm_off_first=by_layer[kinds.index(MAMBA)],
               attn_off_first=by_layer[kinds.index(WINDOW)])
    # "distinct" is not compared: how varied the served text is
    return dict(off, gap_max=float(gaps.max()), gap_mean=float(gaps.mean()),
                not_first_share=float((gaps > 0).mean()),
                own_slot_share=float(along / moved_by),
                own_slot_by_layer=own_slot,
                off_by_layer=by_layer, ssm_out_off=max(scan["out"]),
                ssm_state_off=max(scan["state"]),
                tokens=int(gaps.size), rows=int(rows.size),
                distinct=int(np.unique(tokens).size))


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
              "%.4f%% not the reference's first, gap_max %.4f; of the last "
              "step, by layer (%s), off by %s; the scans' outputs by at "
              "most %.5f, their states by %.5f; the cross layers carry %s "
              "of their own slot"
              % (picked, got["tokens"], got["rows"], got["distinct"],
                 100 * got["not_first_share"], got["gap_max"],
                 "".join(k[0] for k in model.kinds(run.config)),
                 ", ".join("%.5f" % v for v in got["off_by_layer"]),
                 got["ssm_out_off"], got["ssm_state_off"],
                 ", ".join("%.3f" % v for v in got["own_slot_by_layer"])),
              flush=True)
        for name in sorted(set(limits) - {"why"}):
            checks["%s %.6g over %d rows of call %d, limit %.6g"
                   % (name, got[name], got["rows"], picked,
                      limits[name])] = got[name] <= limits[name]
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
    if session_len + prompt_len + gen_len > cfg["serve_positions"] \
            or session_len % workload["reference_turn"] \
            or workload["batch"] != workload["documents"] \
            * workload["questions_a_document"]:
        raise SystemExit(
            "benchmark: a session of %d positions, a prompt of %d and %d "
            "generated tokens do not fit %d cache positions, the session "
            "is not whole turns of %d, or %d rows are not %d documents x "
            "%d questions"
            % (session_len, prompt_len, gen_len, cfg["serve_positions"],
               workload["reference_turn"], workload["batch"],
               workload["documents"], workload["questions_a_document"]))
    built = build(run, model)
    with run.clock.phase("prompts"):
        pool = model.prompts(cfg, workload, run.seed)
        documents = model.documents(cfg, workload, run.seed)
    with run.clock.phase("session"):
        init, inputs = make_session(run, model, documents)
    generate = serve(run, model, init, built)
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
        yoco_calls=len(calls),
        yoco_call_ms=(end - start) / len(calls) * 1e3,
        yoco_batch=workload["batch"], yoco_session_len=session_len,
        yoco_prompt_len=prompt_len, yoco_gen_len=gen_len,
        compiles_in_window=compiled)
    print("window: %d calls in %.3f s, %.1f ms a call, %.2f tok/s per chip"
          % (len(calls), end - start, facts["yoco_call_ms"], rate),
          flush=True)

    if run.trace:
        before = run.compiles.snapshot()
        with run.tracing():
            traced, (t0, t1) = share.window(run, generate, pool, 0.0,
                                            1 + len(calls))
        calls += traced
        facts["compiles_in_window"] += \
            run.compiles.since(before)["compiles"]
        facts.update(yoco_traced_call_ms=(t1 - t0) * 1e3,
                     yoco_step_applications=prompt_len + gen_len - 1)
        print("traced call %.1f ms (tracing costs %+.2f%% a call); the "
              "session's way to the device, once in set-up, %.1f ms"
              % (facts["yoco_traced_call_ms"],
                 (facts["yoco_traced_call_ms"] / facts["yoco_call_ms"]
                  - 1) * 100, facts["yoco_restore_ms"]), flush=True)

    facts["memory_peak_bytes"] = harness.memory_peak_bytes(run.devices)
    print("memory: %s" % {k: v for k, v in
                          (run.devices[0].memory_stats() or {}).items()
                          if "peak" in k or "limit" in k}, flush=True)
    del generate
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
