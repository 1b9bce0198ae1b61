"""KV-cached transformer decoding (cached_attention op).

The cached step program re-uses the scope trained by the full training
program (per-program name scopes align the parameters), and its O(1)
per-token attention must agree with the full causal forward: after
greedy generation through `fluid.ProgramDecoder`, every generated
token equals the argmax of the training program's logits at the
corresponding position of the final sequence (teacher-forced check —
if the cache scattered or masked wrongly, the trajectories diverge).
"""

import numpy as np
import pytest

import paddle_tpu.fluid as fluid
from paddle_tpu.fluid.executor import scope_guard, global_scope
from paddle_tpu.core.scope import Scope
from paddle_tpu.models.transformer_program import (
    build_transformer_program, build_transformer_cached_step_program,
    transformer_program_feeds)

B, T, V, L, H, D = 4, 16, 32, 2, 2, 16


def _train(steps=6):
    main, startup, avg_loss, _ = build_transformer_program(
        B, T, V, n_layer=L, n_head=H, d_model=D)
    with fluid.program_guard(main, startup):
        fluid.optimizer.Adam(learning_rate=5e-3).minimize(avg_loss)
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(startup)
    for i in range(steps):
        exe.run(main, feed=transformer_program_feeds(B, T, V, seed=i),
                fetch_list=[avg_loss])
    return exe


def test_cached_decode_matches_full_forward():
    with scope_guard(Scope()):
        exe = _train()

        step_prog, _, logits, state_pairs = \
            build_transformer_cached_step_program(
                B, T, V, n_layer=L, n_head=H, d_model=D)
        dec = fluid.ProgramDecoder(
            step_prog.clone(for_test=True), token_name="tok",
            logits_name=logits.name, state_pairs=state_pairs)

        bos, gen_len = 3, 8
        d_head = D // H
        init = {"pos": np.zeros((B,), np.int64)}
        for i in range(L):
            init["k_cache_%d" % i] = np.zeros((B, H, T, d_head),
                                              np.float32)
            init["v_cache_%d" % i] = np.zeros((B, H, T, d_head),
                                              np.float32)
        toks, _ = dec.greedy(bos=bos, eos=V + 1, max_len=gen_len,
                             batch_size=B, init_state=init)
        assert toks.shape == (B, gen_len)

        # teacher-forced check against the FULL training program: at
        # position t the causal forward of [bos, toks[:-1]] must argmax
        # to toks[t]
        full = np.concatenate(
            [np.full((B, 1), bos, np.int64), toks[:, :-1]], axis=1)
        pad = np.zeros((B, T - full.shape[1]), np.int64)
        tokens = np.concatenate([full, pad], axis=1)
        infer_main, _, _, full_logits = build_transformer_program(
            B, T, V, n_layer=L, n_head=H, d_model=D)
        got_logits, = exe.run(
            infer_main.clone(for_test=True),
            feed={"tokens": tokens,
                  "positions": transformer_program_feeds(
                      B, T, V)["positions"],
                  "targets": np.zeros((B, T, 1), np.int64)},
            fetch_list=[full_logits])
        got_logits = np.asarray(got_logits)
        for t in range(gen_len):
            want = np.argmax(got_logits[:, t, :], axis=-1)
            np.testing.assert_array_equal(toks[:, t], want,
                                          err_msg="position %d" % t)

        # beam over the cached program: state expansion repeats the
        # per-row pos/caches; beam(1) equals greedy
        seqs, scores = dec.beam(beam_size=1, bos=bos, eos=V + 1,
                                max_len=gen_len, batch_size=B,
                                init_state=init)
        np.testing.assert_array_equal(seqs[:, 0, :], toks)
        assert np.all(np.isfinite(scores))


def test_cached_prefill_continuation_matches_full_forward():
    """Prompt prefill: warm the caches with a prompt in one scan, then
    generate — every token (incl. the first, predicted from the prompt)
    must match the full causal forward teacher-forced on the combined
    sequence."""
    with scope_guard(Scope()):
        exe = _train()

        step_prog, _, logits, state_pairs = \
            build_transformer_cached_step_program(
                B, T, V, n_layer=L, n_head=H, d_model=D)
        dec = fluid.ProgramDecoder(
            step_prog.clone(for_test=True), token_name="tok",
            logits_name=logits.name, state_pairs=state_pairs,
            max_positions=T)

        P, gen_len = 5, 6
        d_head = D // H
        rs = np.random.RandomState(7)
        prompt = rs.randint(0, V, size=(B, P)).astype(np.int64)
        init = {"pos": np.zeros((B,), np.int64)}
        for i in range(L):
            init["k_cache_%d" % i] = np.zeros((B, H, T, d_head),
                                              np.float32)
            init["v_cache_%d" % i] = np.zeros((B, H, T, d_head),
                                              np.float32)
        toks, _ = dec.greedy(bos=0, eos=V + 1, max_len=gen_len,
                             batch_size=B, init_state=init,
                             prompt=prompt)
        assert toks.shape == (B, gen_len)

        # overrunning the cache extent is an error, not silent clamping
        import pytest
        with pytest.raises(ValueError, match="extent"):
            dec.greedy(bos=0, eos=V + 1, max_len=T + 2, batch_size=B,
                       init_state=init, prompt=prompt)

        # prompted sampling at near-zero temperature reproduces the
        # prompted greedy trajectory through the same caches
        cold, _ = dec.sample(bos=0, eos=V + 1, max_len=gen_len,
                             batch_size=B, init_state=init,
                             prompt=prompt, temperature=1e-5)
        np.testing.assert_array_equal(cold, toks)

        # max_len=1: just the prompt's single continuation token
        one, one_len = dec.greedy(bos=0, eos=V + 1, max_len=1,
                                  batch_size=B, init_state=init,
                                  prompt=prompt)
        np.testing.assert_array_equal(one[:, 0], toks[:, 0])
        assert one.shape == (B, 1)

        # empty prompts are rejected up front
        with pytest.raises(ValueError, match="P>=1"):
            dec.greedy(bos=0, eos=V + 1, max_len=2, batch_size=B,
                       init_state=init,
                       prompt=np.zeros((B, 0), np.int64))

        # teacher-forced: full forward over [prompt, toks[:-1]]; the
        # argmax at positions P-1 .. P+gen_len-2 must reproduce toks
        seq = np.concatenate([prompt, toks[:, :-1]], axis=1)
        tokens = np.concatenate(
            [seq, np.zeros((B, T - seq.shape[1]), np.int64)], axis=1)
        infer_main, _, _, full_logits = build_transformer_program(
            B, T, V, n_layer=L, n_head=H, d_model=D)
        got_logits, = exe.run(
            infer_main.clone(for_test=True),
            feed={"tokens": tokens,
                  "positions": transformer_program_feeds(
                      B, T, V)["positions"],
                  "targets": np.zeros((B, T, 1), np.int64)},
            fetch_list=[full_logits])
        got_logits = np.asarray(got_logits)
        for t in range(gen_len):
            want = np.argmax(got_logits[:, P - 1 + t, :], axis=-1)
            np.testing.assert_array_equal(toks[:, t], want,
                                          err_msg="position %d" % t)


def test_cached_attention_op_matches_dense_reference():
    """Direct op check: running the cache step T times equals dense
    causal attention over the same sequence."""
    import jax.numpy as jnp
    from paddle_tpu.ops.registry import get_op_info

    rs = np.random.RandomState(0)
    b, h, t, dh = 2, 2, 6, 4
    d = h * dh
    q = rs.randn(b, t, d).astype(np.float32)
    k = rs.randn(b, t, d).astype(np.float32)
    v = rs.randn(b, t, d).astype(np.float32)

    kernel = get_op_info("cached_attention").kernel
    kc = jnp.zeros((b, h, t, dh))
    vc = jnp.zeros((b, h, t, dh))
    outs = []
    for pos in range(t):
        r = kernel(None, {
            "Q": [jnp.asarray(q[:, pos:pos + 1])],
            "KNew": [jnp.asarray(k[:, pos:pos + 1])],
            "VNew": [jnp.asarray(v[:, pos:pos + 1])],
            "KCache": [kc], "VCache": [vc],
            "Position": [jnp.asarray([pos])]}, {"num_heads": h})
        kc, vc = r["KCacheOut"][0], r["VCacheOut"][0]
        outs.append(np.asarray(r["Out"][0]))
    got = np.concatenate(outs, axis=1)          # [b, t, d]

    ref = _dense_reference(q, k, v, h)
    np.testing.assert_allclose(got, ref, atol=2e-5)


# -- the op over a block of positions ---------------------------------------

def _dense_reference(q, k, v, h):
    import jax.numpy as jnp
    from paddle_tpu.kernels.flash_attention import reference_attention

    b, t, d = q.shape

    def heads(x):
        return jnp.asarray(x.reshape(b, t, h, d // h).transpose(0, 2, 1, 3))

    ref = reference_attention(heads(q), heads(k), heads(v), None, True)
    return np.asarray(ref).transpose(0, 2, 1, 3).reshape(b, t, d)


@pytest.mark.parametrize("first", [0, 5])
@pytest.mark.parametrize("block", [1, 3, 8])
def test_cached_attention_op_over_a_block(block, first):
    """One application at `Position` = `first` over `block` positions,
    the slots before it filled a position at a time: the block's rows
    of dense causal attention over the whole sequence, the block's
    slots written and no other touched."""
    import jax.numpy as jnp
    from paddle_tpu.ops.registry import get_op_info

    rs = np.random.RandomState(block * 16 + first)
    b, h, dh, extent = 2, 2, 4, 16
    t = first + block
    q, k, v = (rs.randn(b, t, h * dh).astype(np.float32) for _ in range(3))
    kernel = get_op_info("cached_attention").kernel

    def apply(kc, vc, lo, hi):
        r = kernel(None, {
            "Q": [jnp.asarray(q[:, lo:hi])],
            "KNew": [jnp.asarray(k[:, lo:hi])],
            "VNew": [jnp.asarray(v[:, lo:hi])],
            "KCache": [kc], "VCache": [vc],
            "Position": [jnp.asarray([lo])]}, {"num_heads": h})
        return r["Out"][0], r["KCacheOut"][0], r["VCacheOut"][0]

    kc = vc = jnp.zeros((b, h, extent, dh))
    for pos in range(first):
        _, kc, vc = apply(kc, vc, pos, pos + 1)
    out, kc, vc = apply(kc, vc, first, t)
    assert out.shape == (b, block, h * dh)
    np.testing.assert_allclose(
        np.asarray(out), _dense_reference(q, k, v, h)[:, first:],
        atol=2e-5)
    for cache, new in ((kc, k), (vc, v)):
        want = np.zeros((b, h, extent, dh), np.float32)
        want[:, :, :t] = new.reshape(b, t, h, dh).transpose(0, 2, 1, 3)
        np.testing.assert_array_equal(np.asarray(cache), want)


def test_cached_attention_declares_an_open_block_axis():
    """The op's outputs are declared from its inputs, not by tracing it:
    a block axis left open stays -1 and the caches keep their extent."""
    main = fluid.Program()
    with fluid.program_guard(main, fluid.Program()):
        q, k, v = (fluid.layers.data(name=n, shape=[B, -1, D],
                                     dtype="float32",
                                     append_batch_size=False)
                   for n in "qkv")
        kc, vc = (fluid.layers.data(name=n, shape=[B, H, T, D // H],
                                    dtype="float32",
                                    append_batch_size=False)
                  for n in ("kc", "vc"))
        pos = fluid.layers.data(name="pos", shape=[-1], dtype="int64",
                                append_batch_size=False)
        out, kc_out, vc_out = fluid.layers.cached_attention(
            q, k, v, kc, vc, pos, num_heads=H)
    assert tuple(out.shape) == (B, -1, D)
    assert tuple(kc_out.shape) == tuple(vc_out.shape) == (B, H, T, D // H)
    assert out.dtype == q.dtype and kc_out.dtype == kc.dtype


def test_cached_step_program_declares_a_block_of_tokens():
    main, _, logits, _ = build_transformer_cached_step_program(
        B, T, V, n_layer=L, n_head=H, d_model=D)
    assert tuple(main.global_block().var("tok").shape) == (B, -1)
    assert tuple(logits.shape) == (B, V)


# -- prefill in blocks against the scan of single positions -------------------

BLOCK = 4
PROMPTS = [1, BLOCK - 1, BLOCK, BLOCK + 1, 2 * BLOCK + 3]
GEN = 5
PROBE = "probe.logits"


def _one_token_step_program():
    """The cached step as it was before it took a block: `tok` [batch],
    one position an application, the ops in the builder's order, so the
    same scope serves both (its reshapes leave the rows open, as the
    builder's now do, so that beam search can feed batch * beam)."""
    d_head = D // H
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        tok = fluid.layers.data(name="tok", shape=[B], dtype="int32",
                                append_batch_size=False)
        pos = fluid.layers.data(name="pos", shape=[-1], dtype="int64",
                                append_batch_size=False)
        caches = [tuple(fluid.layers.data(
            name="%s_cache_%d" % (kind, i), shape=[B, H, T, d_head],
            dtype="float32", append_batch_size=False) for kind in "kv")
            for i in range(L)]
        tok64 = fluid.layers.reshape(
            x=fluid.layers.cast(tok, "int64"), shape=[-1, 1, 1])
        pos_ids = fluid.layers.reshape(x=fluid.layers.reduce_max(pos),
                                       shape=[1, 1, 1])
        x = fluid.layers.embedding(tok64, size=[V, D]) \
            + fluid.layers.embedding(pos_ids, size=[T, D])
        pairs = []
        for i in range(L):
            h = fluid.layers.layer_norm(x, begin_norm_axis=2)
            qkv = fluid.layers.fc(input=h, size=3 * D, num_flatten_dims=2)
            q, k, v = fluid.layers.split(qkv, num_or_sections=3, dim=-1)
            o, kc, vc = fluid.layers.cached_attention(
                q, k, v, caches[i][0], caches[i][1], pos, num_heads=H)
            pairs += [("k_cache_%d" % i, kc.name),
                      ("v_cache_%d" % i, vc.name)]
            x = x + fluid.layers.fc(input=o, size=D, num_flatten_dims=2)
            h = fluid.layers.layer_norm(x, begin_norm_axis=2)
            h = fluid.layers.fc(input=h, size=4 * D, num_flatten_dims=2,
                                act="relu")
            x = x + fluid.layers.fc(input=h, size=D, num_flatten_dims=2)
        x = fluid.layers.layer_norm(x, begin_norm_axis=2)
        logits = fluid.layers.reshape(
            x=fluid.layers.fc(input=x, size=V, num_flatten_dims=2),
            shape=[-1, V])
        pairs.append(("pos", fluid.layers.increment(
            pos, value=1, in_place=False).name))
    return main, logits, pairs


@pytest.fixture(scope="module")
def decoders():
    """(a decoder over the block-taking step, one over the one-token
    step) on one scope of start-up weights, `PREFILL_BLOCK` at BLOCK;
    both carry the step's logits out as a state pair they only write."""
    from paddle_tpu.models import decode

    patch = pytest.MonkeyPatch()
    patch.setattr(decode, "PREFILL_BLOCK", BLOCK)
    with scope_guard(Scope()):
        block_prog, startup, logits, pairs = \
            build_transformer_cached_step_program(
                B, T, V, n_layer=L, n_head=H, d_model=D)
        fluid.Executor(fluid.CPUPlace()).run(startup)
        one_prog, one_logits, one_pairs = _one_token_step_program()
        made = tuple(
            fluid.ProgramDecoder(
                prog.clone(for_test=True), token_name="tok",
                logits_name=out.name,
                state_pairs=list(wires) + [(PROBE, out.name)],
                max_positions=T)
            for prog, out, wires in ((block_prog, logits, pairs),
                                     (one_prog, one_logits, one_pairs)))
    yield made
    patch.undo()


def _empty_state():
    init = {"pos": np.zeros((B,), np.int64),
            PROBE: np.zeros((B, V), np.float32)}
    for i in range(L):
        for kind in "kv":
            init["%s_cache_%d" % (kind, i)] = np.zeros(
                (B, H, T, D // H), np.float32)
    return init


def _prompt(length):
    return np.random.RandomState(length).randint(0, V, size=(B, length))


def test_decoder_reads_the_token_feeds_declaration(decoders):
    in_blocks, one_token = decoders
    assert in_blocks._takes_block and not one_token._takes_block


@pytest.mark.parametrize("length", PROMPTS)
def test_block_prefill_leaves_the_scanned_prefills_state(decoders, length):
    """After the prompt alone: the same first token, the position
    advanced by the prompt, the last position's logits to 1e-5, the
    first layer's caches bit for bit (their keys and values come from
    the embeddings through one LayerNorm and one product) and the
    deeper ones, which read an attention output that differs in its
    last bits, to 1e-5; no slot past the prompt written."""
    names = sorted(_empty_state())
    got, want = (dec.greedy(bos=0, eos=V + 1, max_len=1,
                            init_state=_empty_state(),
                            prompt=_prompt(length), return_state=names)
                 for dec in decoders)
    np.testing.assert_array_equal(got[0], want[0])
    assert got[0].shape == (B, 1)
    np.testing.assert_array_equal(got[2]["pos"], np.full((B,), length))
    np.testing.assert_array_equal(want[2]["pos"], np.full((B,), length))
    np.testing.assert_allclose(got[2][PROBE], want[2][PROBE], atol=1e-5)
    assert np.abs(want[2][PROBE]).max() > 0
    for name in names:
        if "cache" not in name:
            continue
        if name.endswith("_0"):
            np.testing.assert_array_equal(got[2][name], want[2][name])
        else:
            np.testing.assert_allclose(got[2][name], want[2][name],
                                       atol=1e-5)
        assert np.abs(got[2][name][:, :, :length]).min() > 0
        assert not got[2][name][:, :, length:].any()


@pytest.mark.parametrize("length", PROMPTS)
@pytest.mark.parametrize("mode", ["greedy", "sample", "one"])
def test_block_prefill_serves_the_scanned_prefills_tokens(decoders, mode,
                                                          length):
    def served(dec):
        kwargs = dict(bos=0, eos=V + 1, init_state=_empty_state(),
                      prompt=_prompt(length))
        if mode == "sample":
            return dec.sample(max_len=GEN, seed=11, temperature=0.8,
                              top_k=5, **kwargs)
        return dec.greedy(max_len=1 if mode == "one" else GEN, **kwargs)

    (toks, lengths), (want, want_lengths) = (served(d) for d in decoders)
    assert toks.shape == (B, 1 if mode == "one" else GEN)
    np.testing.assert_array_equal(toks, want)
    np.testing.assert_array_equal(lengths, want_lengths)


def test_beam_on_a_block_taking_step_is_beam_on_the_one_token_step(decoders):
    (seqs, scores), (want, want_scores) = (
        dec.beam(beam_size=3, bos=2, eos=V + 1, max_len=GEN,
                 batch_size=B, init_state=_empty_state(),
                 length_penalty=0.5) for dec in decoders)
    assert seqs.shape == (B, 3, GEN)
    np.testing.assert_array_equal(seqs, want)
    np.testing.assert_allclose(scores, want_scores, atol=1e-5)


# -- the counters ----------------------------------------------------------------

def _lowered(dec, length):
    import jax
    import jax.numpy as jnp
    from paddle_tpu.models import decode
    from paddle_tpu.obs import telemetry

    state = {k: jnp.asarray(v) for k, v in _empty_state().items()}

    def call(params, state, prompt):
        step = dec._step_fn(params)
        state, first = decode.prefill(step, state, prompt,
                                      dec._takes_block)
        return decode.greedy_decode(step, state, first, V + 1, GEN - 1, B)

    before = telemetry.snapshot()
    jax.make_jaxpr(call)(dec._params, state, jnp.asarray(_prompt(length)))
    return {k: v for k, v in telemetry.snapshot_delta(before).items()
            if k.startswith(("prefill_lowerings_total",
                             "cached_attention_lowerings_total"))}


def test_counters_say_block_prefill_and_its_block_lengths(decoders):
    """2 x BLOCK + 3 positions: a block of 3 first, then one scan over
    the two blocks of BLOCK, then the decoding scan a position at a
    time; an op instance a traced body holds counts once."""
    assert _lowered(decoders[0], 2 * BLOCK + 3) == {
        "prefill_lowerings_total{block=%d,form=block}" % BLOCK: 1,
        "cached_attention_lowerings_total{block=3}": L,
        "cached_attention_lowerings_total{block=%d}" % BLOCK: L,
        "cached_attention_lowerings_total{block=1}": L}
    # no remainder: the scan's body alone, traced once
    assert _lowered(decoders[0], 2 * BLOCK) == {
        "prefill_lowerings_total{block=%d,form=block}" % BLOCK: 1,
        "cached_attention_lowerings_total{block=%d}" % BLOCK: L,
        "cached_attention_lowerings_total{block=1}": L}


def test_counters_say_which_way_a_gpt2_step_takes_over_its_caches():
    """The GPT-2 cached step at the decode cell's kind of shape (heads
    of 64, a multiple of 128 slots, caches in the weights' type): a
    decode step's op instances count the kernel, a prefill block's the
    plain path, `cached_attention_lowerings_total{block}` as before."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.models import decode
    from paddle_tpu.obs import telemetry

    layers, heads, slots, rows = 2, 2, 256, 2
    main, startup, logits, pairs = build_transformer_cached_step_program(
        rows, slots, V, n_layer=layers, n_head=heads, d_model=64 * heads,
        d_ff=64)
    scope = fluid.Scope()
    fluid.Executor(fluid.CPUPlace()).run(startup, scope=scope)
    dec = fluid.ProgramDecoder(main.clone(for_test=True), token_name="tok",
                               logits_name=logits.name, state_pairs=pairs,
                               scope=scope, max_positions=slots)
    state = {feed: jnp.zeros((rows, heads, slots, 64), jnp.float32)
             for feed, _ in pairs if feed != "pos"}
    state["pos"] = jnp.zeros((rows,), jnp.int32)

    def call(params, state, prompt):
        step = dec._step_fn(params)
        state, first = decode.prefill(step, state, prompt, dec._takes_block)
        return decode.greedy_decode(step, state, first, V + 1, 3, rows)

    block = decode.PREFILL_BLOCK    # the module's fixture may hold it down
    before = telemetry.snapshot()
    jax.make_jaxpr(call)(dec._params, state,
                         jnp.zeros((rows, block), jnp.int32))
    delta = telemetry.snapshot_delta(before)
    label = "window_attention_lowerings_total{block=%d,block_k=%d," \
        "kind=full,kv_heads=%d,path=%s,step_heads=%d,step_rows=%d,window=0}"
    assert {k: v for k, v in delta.items()
            if k.startswith(("window_attention_lowerings_total",
                             "cached_attention_lowerings_total"))} == {
        # a grid step of the 64-wide kernel takes both rows' two heads
        label % (1, 256, heads, "kernel", heads, rows): layers,
        label % (block, 0, heads, "plain", 1, 1): layers,
        "cached_attention_lowerings_total{block=1}": layers,
        "cached_attention_lowerings_total{block=%d}" % block: layers}


def test_counters_say_a_one_token_step_is_scanned(decoders):
    """A `[batch]` token feed keeps the scan of single positions: the
    first outside the scan, the scan's body, the decoding scan's."""
    assert _lowered(decoders[1], 2 * BLOCK + 3) == {
        "prefill_lowerings_total{block=1,form=step}": 1,
        "cached_attention_lowerings_total{block=1}": 3 * L}


def test_cached_step_serves_in_the_scopes_type(decoders):
    """On bfloat16 weights the step's logits and caches are bfloat16:
    the float32 the embeddings' sum is held in ends with the first
    block's residual add and does not ride on through the layers."""
    import jax
    import jax.numpy as jnp

    dec = decoders[0]
    params = {k: v.astype(jnp.bfloat16) for k, v in dec._params.items()}
    state = {k: jnp.asarray(v, jnp.bfloat16 if "cache" in k else None)
             for k, v in _empty_state().items()}
    logits, new = jax.eval_shape(dec._step_fn(params), state,
                                 jnp.asarray(_prompt(3)))
    assert logits.dtype == jnp.bfloat16 and logits.shape == (B, V)
    assert all(v.dtype == jnp.bfloat16 for k, v in new.items()
               if "cache" in k)
    # and the sum itself is float32: the first LayerNorm reads it so
    jaxpr = str(jax.make_jaxpr(dec._step_fn(params))(
        state, jnp.asarray(_prompt(3))))
    assert "f32[%d,3,%d]" % (B, D) in jaxpr
