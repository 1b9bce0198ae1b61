"""ProgramDecoder: compiled generation from a single-step fluid Program.

A tiny RNN LM is trained through the executor; the SAME step program
then generates via (a) ProgramDecoder (one jitted scan, the deploy hot
path) and (b) a per-step executor loop (how the host-op path steps) —
greedy outputs must match token for token, and beam(1) must equal
greedy.
"""

import numpy as np

import paddle_tpu.fluid as fluid

V, E, H = 23, 12, 16
BOS, EOS = 1, 0


def _build_step_program():
    """One decode step: token [B] + hidden [B,H] -> logits [B,V] +
    new hidden."""
    main = fluid.Program()
    startup = fluid.Program()
    with fluid.program_guard(main, startup):
        tok = fluid.layers.data(name="tok", shape=[-1], dtype="int64",
                                append_batch_size=False)
        h_in = fluid.layers.data(name="h_in", shape=[-1, H],
                                 dtype="float32", append_batch_size=False)
        emb = fluid.layers.embedding(tok, size=[V, E])
        h_out = fluid.layers.fc(input=[emb, h_in], size=H, act="tanh")
        logits = fluid.layers.fc(input=h_out, size=V, act=None)
    return main, startup, tok, h_in, h_out, logits


def _train(main, startup, logits_name, steps=30):
    """A few SGD steps on random next-token data so weights are
    non-initial (generation must reflect training)."""
    train_prog = main.clone()
    with fluid.program_guard(train_prog, startup):
        label = fluid.layers.data(name="label", shape=[-1, 1],
                                  dtype="int64", append_batch_size=False)
        logits_var = train_prog.global_block().var(logits_name)
        loss = fluid.layers.mean(
            x=fluid.layers.softmax_with_cross_entropy(logits_var, label))
        fluid.optimizer.SGD(learning_rate=0.1).minimize(loss)
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(startup)
    rs = np.random.RandomState(0)
    for _ in range(steps):
        feed = {"tok": rs.randint(0, V, size=(8,)).astype(np.int64),
                "h_in": rs.randn(8, H).astype(np.float32),
                "label": rs.randint(0, V, size=(8, 1)).astype(np.int64)}
        exe.run(train_prog, feed=feed, fetch_list=[loss])
    return exe


def _greedy_by_executor_loop(exe, main, logits, h_out, batch, max_len):
    """Per-step fetch loop — the shape of the host-op generation path."""
    tok = np.full((batch,), BOS, np.int64)
    h = np.zeros((batch, H), np.float32)
    done = np.zeros((batch,), bool)
    out = []
    for _ in range(max_len):
        lg, h = exe.run(main, feed={"tok": tok, "h_in": h},
                        fetch_list=[logits, h_out])
        nxt = np.argmax(np.asarray(lg), axis=-1).astype(np.int64)
        nxt = np.where(done, EOS, nxt)
        done |= nxt == EOS
        out.append(nxt)
        tok = nxt
    return np.stack(out, axis=1)


def test_program_decoder_matches_executor_loop():
    main, startup, tok, h_in, h_out, logits = _build_step_program()
    exe = _train(main, startup, logits.name)

    batch, max_len = 5, 12
    dec = fluid.ProgramDecoder(main, token_name="tok",
                               logits_name=logits.name,
                               state_pairs=[("h_in", h_out.name)])
    toks, lengths = dec.greedy(
        bos=BOS, eos=EOS, max_len=max_len,
        init_state={"h_in": np.zeros((batch, H), np.float32)})

    want = _greedy_by_executor_loop(exe, main, logits, h_out, batch,
                                    max_len)
    np.testing.assert_array_equal(toks, want)
    assert lengths.shape == (batch,)

    # beam(1) == greedy on the same program
    seqs, scores = dec.beam(
        beam_size=1, bos=BOS, eos=EOS, max_len=max_len,
        init_state={"h_in": np.zeros((batch, H), np.float32)})
    np.testing.assert_array_equal(seqs[:, 0, :], toks)
    assert np.all(np.isfinite(scores))


def test_program_decoder_sampling():
    """Temperature→0 sampling converges to greedy; temperature 1 with
    different seeds diversifies; top_k=1 equals greedy by definition."""
    main, startup, tok, h_in, h_out, logits = _build_step_program()
    _train(main, startup, logits.name)
    dec = fluid.ProgramDecoder(main, token_name="tok",
                               logits_name=logits.name,
                               state_pairs=[("h_in", h_out.name)])
    batch, max_len = 5, 10
    init = {"h_in": np.zeros((batch, H), np.float32)}

    greedy, _ = dec.greedy(bos=BOS, eos=EOS, max_len=max_len,
                           init_state=init)
    cold, _ = dec.sample(bos=BOS, eos=EOS, max_len=max_len,
                         init_state=init, temperature=1e-5)
    np.testing.assert_array_equal(cold, greedy)
    top1, _ = dec.sample(bos=BOS, eos=EOS, max_len=max_len,
                         init_state=init, top_k=1)
    np.testing.assert_array_equal(top1, greedy)

    a, _ = dec.sample(bos=BOS, eos=EOS, max_len=max_len,
                      init_state=init, seed=1, temperature=1.5)
    b, _ = dec.sample(bos=BOS, eos=EOS, max_len=max_len,
                      init_state=init, seed=2, temperature=1.5)
    assert not np.array_equal(a, b), "different seeds should diverge"
    assert ((a >= 0) & (a < V)).all()


def test_program_decoder_beam_orders_scores():
    main, startup, tok, h_in, h_out, logits = _build_step_program()
    _train(main, startup, logits.name)
    dec = fluid.ProgramDecoder(main, token_name="tok",
                               logits_name=logits.name,
                               state_pairs=[("h_in", h_out.name)])
    seqs, scores = dec.beam(
        beam_size=3, bos=BOS, eos=EOS, max_len=8,
        init_state={"h_in": np.zeros((4, H), np.float32)})
    assert seqs.shape == (4, 3, 8)
    # best-first ordering per source
    assert np.all(np.diff(scores, axis=1) <= 1e-6)


def test_a_recurrent_state_step_prefills_a_position_at_a_time():
    """A `[batch]` token feed: the decoder scans the prompt through the
    step one position at a time (the counter says so), and the state it
    reaches is the state of feeding the prompt by hand."""
    from paddle_tpu.obs import telemetry

    main, startup, tok, h_in, h_out, logits = _build_step_program()
    exe = _train(main, startup, logits.name)
    infer = main.clone(for_test=True)
    dec = fluid.ProgramDecoder(infer, token_name="tok",
                               logits_name=logits.name,
                               state_pairs=[("h_in", h_out.name)])
    assert not dec._takes_block
    prompt = np.array([[3, 5, 7], [2, 4, 6]], np.int64)
    before = telemetry.snapshot()
    toks, _ = dec.greedy(bos=BOS, eos=EOS, max_len=6,
                         init_state={"h_in": np.zeros((2, H), np.float32)},
                         prompt=prompt)
    counted = {k: v for k, v in telemetry.snapshot_delta(before).items()
               if k.startswith("prefill_lowerings_total")}
    assert counted == {"prefill_lowerings_total{block=1,form=step}": 1}

    h = np.zeros((2, H), np.float32)
    for t in range(prompt.shape[1]):
        lg, h = exe.run(infer, feed={"tok": prompt[:, t], "h_in": h},
                        fetch_list=[logits, h_out])
    np.testing.assert_array_equal(toks[:, 0], np.argmax(lg, axis=-1))


def _decoder_counters(before):
    """What the `decoder_*` counters rose by since `before`."""
    from paddle_tpu.obs import telemetry

    return {k: v for k, v in telemetry.snapshot_delta(before).items()
            if k.startswith("decoder_")}


def _untrained_decoder():
    main, startup, tok, h_in, h_out, logits = _build_step_program()
    scope = fluid.Scope()
    fluid.Executor(fluid.CPUPlace()).run(startup, scope=scope)
    return fluid.ProgramDecoder(main.clone(for_test=True), token_name="tok",
                                logits_name=logits.name,
                                state_pairs=[("h_in", h_out.name)],
                                scope=scope)


def test_two_calls_are_one_program_and_their_tokens_and_bytes_by_source():
    """A state handed over as a numpy array, then as a `jax.Array`: two
    calls of one program, the tokens asked for, the state's bytes by
    where they were, and seconds in every phase."""
    import jax.numpy as jnp

    from paddle_tpu.obs import telemetry

    dec = _untrained_decoder()
    batch, max_len = 3, 7
    prompt = np.array([[3, 5, 7, 2]] * batch, np.int64)
    state = np.zeros((batch, H), np.float32)
    before = telemetry.snapshot()
    for h in (state, jnp.asarray(state)):
        dec.greedy(bos=BOS, eos=EOS, max_len=max_len, prompt=prompt,
                   init_state={"h_in": h})
    rose = _decoder_counters(before)
    seconds = {k: rose.pop(k) for k in list(rose)
               if k.startswith("decoder_seconds_total")}
    assert rose == {
        "decoder_calls_total{mode=greedy-prefill}": 2,
        "decoder_programs_total{mode=greedy-prefill}": 1,
        "decoder_tokens_total{kind=prompt}": 2 * batch * 4,
        "decoder_tokens_total{kind=generated}": 2 * batch * max_len,
        "decoder_state_bytes_total{source=host}": state.nbytes,
        "decoder_state_bytes_total{source=device}": state.nbytes}
    assert sorted(seconds) == [
        "decoder_seconds_total{phase=%s}" % p
        for p in ("dispatch", "fetch", "prep")]
    assert all(v > 0 for v in seconds.values())
    # the first call's dispatch held the trace and the compile
    assert seconds["decoder_seconds_total{phase=dispatch}"] \
        > seconds["decoder_seconds_total{phase=prep}"]


def test_each_mode_counts_its_calls_and_a_beam_its_rows():
    from paddle_tpu.obs import telemetry

    dec = _untrained_decoder()
    init = {"h_in": np.zeros((2, H), np.float32)}
    before = telemetry.snapshot()
    dec.greedy(bos=BOS, eos=EOS, max_len=5, init_state=init)
    dec.sample(bos=BOS, eos=EOS, max_len=4, init_state=init)
    dec.sample(bos=BOS, eos=EOS, max_len=4, init_state=init, seed=3)
    dec.beam(beam_size=3, bos=BOS, eos=EOS, max_len=6, init_state=init)
    rose = _decoder_counters(before)
    assert {k: v for k, v in rose.items() if "seconds" not in k} == {
        "decoder_calls_total{mode=greedy}": 1,
        "decoder_calls_total{mode=sample}": 2,
        "decoder_calls_total{mode=beam}": 1,
        "decoder_programs_total{mode=greedy}": 1,
        "decoder_programs_total{mode=sample}": 1,
        "decoder_programs_total{mode=beam}": 1,
        # no prompt: no prompt tokens; a beam's rows are batch x beam
        "decoder_tokens_total{kind=generated}":
            2 * 5 + 2 * 2 * 4 + 2 * 3 * 6,
        "decoder_state_bytes_total{source=host}": 4 * 2 * H * 4}


def test_a_call_that_is_refused_counts_nothing():
    import pytest

    from paddle_tpu.obs import telemetry

    dec = _untrained_decoder()
    before = telemetry.snapshot()
    with pytest.raises(ValueError, match="init_state missing"):
        dec.greedy(bos=BOS, eos=EOS, max_len=5, batch_size=2)
    assert _decoder_counters(before) == {}
