"""A generation cell whose decoder yields none, one or several tokens a
pass: generation by diffusion over blocks.  `fluid.ProgramDecoder` over a
pipeline stage's cached step Program that takes a block of positions
under a block-causal mask and hands out the logits of every one,
`decoder.diffuse(prompt=<[batch, prompt_len] ids>, max_len=gen_len, ...)`
in a closed loop, one call in flight, each call one lockstep batch that
prefills its prompts' whole blocks and generates a fixed length block by
block: denoising passes that fix positions by confidence, and a commit
pass a block that writes the cache (drivers/decode_program.py's
`model_key`, `make_weights` and `trace_lower_seconds`,
drivers/decode_share.py's `window`, drivers/decode_session.py's `seeded`;
read decode_program.py for the window and the rate).

`decode_tok_per_s` keeps its definition: the generated tokens of whole
calls over the window's seconds.  It is the one number that does not
assume a token a step; what a pass yields is among the per-layer
metrics (`diffusion_tokens_per_pass`).

`correct`, after the window, on what the window itself served: for the
first `reference_rows` rows of one call drawn from the seed and a seeded
`checked_blocks` of their generated blocks, the float32 reference
(benchmark/reference/sdar_moe.py) replays the program's own trajectory,
every denoising pass of those blocks fed what the program fed it (made
from the call's `fixed_pass`), and is compared in logits and not tokens:
`gap_mean` and `not_first_share` (by how much the reference's logit of a
fixed token lies under the reference's best at the positions the pass
fixed, and how often it is not the best); `conf_off` (the program's
confidence against the reference's probability of the same token there,
|ln - ln| in the mean); `other_position_share` (the share of passes that
fixed another position than the masked one the reference ranks first
for the same input); and `kv_off`, the first layer's keys and values as
committed (the first rows' caches, carried out of the call as a state
pair the step only writes) against the reference's whole forward of the
final sequence, root mean square of the difference over the reference's,
the larger of the two.
"""

import gc
import sys

import numpy as np

from benchmark import harness


def serve(run, model):
    """`generate(prompt, max_len) -> (tokens, lengths, info)` on the
    host: `info` is `diffuse`'s, its "state" the probes'."""
    import jax
    import jax.numpy as jnp
    import paddle_tpu.fluid as fluid

    cfg, workload = run.config, run.workload
    shared = run.lookup.module("drivers", "decode_program")
    batch, rows = workload["batch"], workload["reference_rows"]
    with run.clock.phase("build"):
        built = model.build(cfg, batch, rows)
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
    probes = dict(built["probes"].values())
    with run.clock.phase("decoder"):
        decoder = fluid.ProgramDecoder(
            built["main"].clone(for_test=True), token_name="tok",
            logits_name=built["logits"].name,
            state_pairs=built["state_pairs"] + list(probes.items()),
            scope=scope, max_positions=cfg["serve_positions"])
    del scope
    dtype = jnp.dtype(workload["serve_dtype"])
    init = {"pos": np.zeros((batch,), np.int64)}
    init.update({feed: np.zeros(shape, dtype)
                 for feed, shape in built["cache_shapes"].items()})
    kept = (rows,) + next(iter(built["cache_shapes"].values()))[1:]
    init.update({feed: np.zeros(kept, dtype) for feed in probes})
    how = cfg["generation"]

    def generate(prompt, max_len):
        return decoder.diffuse(
            prompt, max_len, how["block_length"], how["denoising_steps"],
            how["remasking"], how["confidence_threshold"],
            how["mask_token_id"], temperature=how["temperature"],
            init_state=init, return_state=sorted(probes))

    return generate


def checked_blocks(run):
    """The generated blocks of a call that `correct` replays:
    `checked_blocks` of them, drawn from the seed, in order (counted
    from the first generated block)."""
    cfg, workload = run.config, run.workload
    size = cfg["generation"]["block_length"]
    blocks = -(-(workload["prompt_len"] % size + workload["gen_len"])
               // size)
    # a last block that reaches past the generated length is not whole
    # in what a call returns
    whole = (workload["prompt_len"] % size + workload["gen_len"]) // size
    rng = np.random.default_rng([run.seed, 0xB10C])
    return np.sort(rng.choice(min(blocks, whole),
                              min(workload["checked_blocks"], whole),
                              replace=False))


def compare(run, model, pool, call):
    """What `correct` can rest on (the module's docstring)."""
    gc.collect()    # the decoder the caller let go of (decode_share)
    cfg, workload = run.config, run.workload
    if "control" in workload:
        # benchmark/tests/diffusion_control.py: the reference made wrong
        # in one named way, which a limit has to refuse
        cfg = dict(cfg, control=workload["control"])
    control = cfg.get("control", {})
    reference = run.lookup.module("reference", workload["reference"])
    ends, block_of = run.lookup.module(
        "drivers", "decode_session").seeded(run, model)
    index, tokens, _, info = call
    how = cfg["generation"]
    size, mask = how["block_length"], how["mask_token_id"]
    rows, length = workload["reference_rows"], workload["prompt_len"]
    whole, left = length // size * size, length % size
    final = np.concatenate([pool[index][:rows], tokens[:rows]], axis=1)
    final = final[:, :final.shape[1] // size * size]
    fixed_pass = info["fixed_pass"][:rows]
    blocks = checked_blocks(run)
    fed = reference.pass_inputs(final, fixed_pass, whole, left, size, mask,
                                blocks)
    keys = sorted(fed)
    wanted = [(row, whole + n * size, fed[n, s][row])
              for n, s in keys for row in range(rows)]
    stored = final
    if control.get("no_commit"):
        # what each block's last denoising pass saw, in the final
        # tokens' place: the cache of a system without a commit pass
        every = range((final.shape[1] - whole) // size)
        seen = reference.pass_inputs(final, fixed_pass, whole, left, size,
                                     mask, every)
        stored = final.copy()
        for n in every:
            last = max(s for m, s in seen if m == n)
            stored[:, whole + n * size:whole + (n + 1) * size] = seen[n, last]
    logits, k0, v0 = reference.replay(cfg, ends, block_of, stored, size,
                                      wanted, whole)
    got = reference.trajectory(
        logits, np.stack([w[2] for w in wanted]),
        *reference.fixed_by(keys, rows, final, fixed_pass,
                            info["fixed_conf"][:rows], whole, length, size),
        mask)
    del logits
    # the caches lie [rows, kv heads, slots, dim]; the reference's [rows,
    # slots, kv heads, dim], over the stored positions
    held = stored.shape[1]
    off = [reference.off(
        np.asarray(info["state"]["probe." + what], np.float32)
        [:, :, :held].transpose(0, 2, 1, 3), want)
        for what, want in (("keys", k0), ("values", v0))]
    got.update(kv_off=max(off), keys_off=off[0], values_off=off[1],
               passes=len(keys), rows=rows, blocks=int(blocks.size),
               distinct=int(np.unique(tokens).size))
    return got


def check(run, model, pool, calls):
    """{text: ok} for the window's calls."""
    cfg, workload = run.config, run.workload
    vocab, how = cfg["vocab_size"], cfg["generation"]
    limits = workload["correct"]
    shape = (workload["batch"], workload["gen_len"])
    blocks = -(-(workload["prompt_len"] % how["block_length"] + shape[1])
               // how["block_length"])
    sound = [tokens.shape == shape and bool((lengths == shape[1]).all())
             and int(tokens.min()) >= 0 and int(tokens.max()) < vocab
             and not (tokens == how["mask_token_id"]).any()
             and info["commit_passes"] == blocks
             and blocks <= info["denoise_passes"]
             <= blocks * how["denoising_steps"]
             and int(info["fixed_pass"].min()) >= 0
             for _, tokens, lengths, info in calls]
    run.failed = workload["batch"] * sound.count(False)
    picked = int(np.random.default_rng([run.seed, 0xC0DE]).integers(
        len(calls)))
    checks = {"%d of %d calls gave %d x %d tokens inside the vocabulary, "
              "none the mask, every position fixed by a pass of its block, %d "
              "blocks "
              "committed, limit %d"
              % (sound.count(True), len(calls), shape[0], shape[1], blocks,
                 len(calls)): all(sound)}
    if sound[picked]:
        with run.clock.phase("reference"):
            got = compare(run, model, pool, calls[picked])
        print("call %d: %d passes of %d blocks of %d rows replayed, %d "
              "positions fixed in them, %.4f%% not the reference's first; "
              "the first layer's keys off by %.5f and values by %.5f of "
              "the reference's; %d distinct tokens in the call"
              % (picked, got["passes"], got["blocks"], got["rows"],
                 got["fixed"], 100 * got["not_first_share"],
                 got["keys_off"], got["values_off"], got["distinct"]),
              flush=True)
        print("compared: %s" % ", ".join(
            "%s %.6g" % (name, got[name]) for name in sorted(got)), flush=True)
        for name in sorted(set(limits) - {"why"}):
            checks["%s %.6g over the %d positions %d passes of call %d "
                   "fixed, limit %.6g"
                   % (name, got[name], got["fixed"], got["passes"], picked,
                      limits[name])] = got[name] <= limits[name]
    return checks


def run(run):
    workload, how = run.workload, run.config["generation"]
    model = run.lookup.module("models", workload["builder"])
    shared = run.lookup.module("drivers", "decode_program")
    window = run.lookup.module("drivers", "decode_share").window
    gen_len = workload["gen_len"]
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
    passes = [call[3]["denoise_passes"] + call[3]["commit_passes"]
              for call in calls]
    facts.update(
        diffusion_calls=len(calls),
        diffusion_call_ms=(end - start) / len(calls) * 1e3,
        diffusion_batch=workload["batch"],
        diffusion_prompt_len=workload["prompt_len"],
        diffusion_gen_len=gen_len,
        diffusion_block_length=how["block_length"],
        diffusion_denoise_passes=calls[0][3]["denoise_passes"],
        diffusion_commit_passes=calls[0][3]["commit_passes"],
        compiles_in_window=compiled)
    print("window: %d calls in %.3f s, %.1f ms a call of %d passes (%d "
          "that denoise, %d that commit), %.3f tokens a row a pass, %.2f "
          "tok/s per chip"
          % (len(calls), end - start, facts["diffusion_call_ms"], passes[0],
             facts["diffusion_denoise_passes"],
             facts["diffusion_commit_passes"], gen_len / passes[0], rate),
          flush=True)

    if run.trace:
        before = run.compiles.snapshot()
        with run.tracing():
            traced, (t0, t1) = window(run, generate, pool, 0.0,
                                      1 + len(calls))
        calls += traced
        facts["compiles_in_window"] += \
            run.compiles.since(before)["compiles"]
        facts["diffusion_traced_call_ms"] = (t1 - t0) * 1e3
        print("traced call %.1f ms (tracing costs %+.2f%% a call)"
              % (facts["diffusion_traced_call_ms"],
                 (facts["diffusion_traced_call_ms"]
                  / facts["diffusion_call_ms"] - 1) * 100), flush=True)

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
