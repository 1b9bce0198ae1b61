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
