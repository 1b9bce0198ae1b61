"""Transformer built through the Program stack (fluid layers).

The GPT-2-shaped decoder (learned positions, LayerNorm, a ReLU MLP)
expressed as a fluid Program; the block open models have been made of
since 2023 (RMSNorm, rotary positions, a gated SiLU MLP, weights shared
across depth) is models/looped_program.py, on the same stack.  Here the
whole framework surface applies: real optimizers with accumulators,
regularizers/clipping, LR schedules, checkpointing, the transpiler, and
`ParallelTrainer` sharding over dp×mp×sp meshes.  Attention is the
registered `flash_attention` op (ops/attention.py) — pallas kernel on
TPU, ring attention over ICI when `sp_axis` names a mesh axis — which
is the in-framework surface the reference lacks (its nets-module
attention materializes the [T,T] matrix, reference:
python/paddle/v2/fluid/nets.py:338).

Activation is relu (the 2018 reference op set has no gelu).
"""

import numpy as np

from .. import fluid

__all__ = ["build_transformer_program",
           "build_transformer_step_program",
           "build_transformer_cached_step_program",
           "transformer_program_feeds"]


def _block(x, n_head, d_model, d_ff, causal, sp_axis, sp_mode):
    h = fluid.layers.layer_norm(x, begin_norm_axis=2)
    qkv = fluid.layers.fc(input=h, size=3 * d_model, num_flatten_dims=2)
    q, k, v = fluid.layers.split(qkv, num_or_sections=3, dim=-1)
    o = fluid.layers.flash_attention(
        q, k, v, num_heads=n_head, causal=causal,
        sequence_parallel_axis=sp_axis,
        sequence_parallel_mode=sp_mode)
    x = x + fluid.layers.fc(input=o, size=d_model, num_flatten_dims=2)

    h = fluid.layers.layer_norm(x, begin_norm_axis=2)
    h = fluid.layers.fc(input=h, size=d_ff, num_flatten_dims=2,
                        act="relu")
    return x + fluid.layers.fc(input=h, size=d_model, num_flatten_dims=2)


def build_transformer_program(batch, seq_len, vocab_size, n_layer=2,
                              n_head=4, d_model=64, d_ff=None,
                              causal=True, sp_axis="", sp_mode="ring"):
    """Returns (main, startup, avg_loss, logits).

    Feeds: tokens/positions int64 [batch, seq_len], targets int64
    [batch, seq_len, 1] (use `transformer_program_feeds`).
    """
    if d_ff is None:
        d_ff = 4 * d_model
    main = fluid.Program()
    startup = fluid.Program()
    with fluid.program_guard(main, startup):
        tokens = fluid.layers.data(
            name="tokens", shape=[batch, seq_len], dtype="int64",
            append_batch_size=False)
        positions = fluid.layers.data(
            name="positions", shape=[batch, seq_len], dtype="int64",
            append_batch_size=False)
        targets = fluid.layers.data(
            name="targets", shape=[batch, seq_len, 1], dtype="int64",
            append_batch_size=False)

        x = fluid.layers.embedding(tokens, size=[vocab_size, d_model]) \
            + fluid.layers.embedding(positions, size=[seq_len, d_model])
        for _ in range(n_layer):
            x = _block(x, n_head, d_model, d_ff, causal, sp_axis, sp_mode)
        x = fluid.layers.layer_norm(x, begin_norm_axis=2)
        logits = fluid.layers.fc(input=x, size=vocab_size,
                                 num_flatten_dims=2)

        flat = fluid.layers.reshape(x=logits, shape=[-1, vocab_size])
        flat_tgt = fluid.layers.reshape(x=targets, shape=[-1, 1])
        loss = fluid.layers.softmax_with_cross_entropy(flat, flat_tgt)
        avg_loss = fluid.layers.mean(x=loss)
    return main, startup, avg_loss, logits


def build_transformer_step_program(batch, window, vocab_size, n_layer=2,
                                   n_head=4, d_model=64, d_ff=None,
                                   sp_axis="", sp_mode="ring"):
    """Sliding-window decode step for `fluid.ProgramDecoder`.

    Feeds: tok [batch] (the token the decoder just chose), window
    [batch, window] int64 (the last `window` tokens), positions
    [batch, window].  Fetches: logits [batch, vocab] for the NEXT
    token, plus the shifted window — wire it as::

        dec = fluid.ProgramDecoder(
            prog.clone(for_test=True), token_name="tok",
            logits_name=logits.name,
            state_pairs=[("window", new_window.name),
                         ("positions", "positions")])

    Because name scopes are per Program, its parameters carry the SAME
    names as a `build_transformer_program` of the same architecture
    (the extra cast/split/concat ops create only temporaries), so a
    scope trained by the training program drives this step program
    directly.  A KV-cache step (O(1) work per token instead of
    O(window)) is the long-context extension; the window form needs no
    cache plumbing and is exact for contexts up to `window`.
    """
    if d_ff is None:
        d_ff = 4 * d_model
    main = fluid.Program()
    startup = fluid.Program()
    with fluid.program_guard(main, startup):
        tok = fluid.layers.data(name="tok", shape=[batch], dtype="int32",
                                append_batch_size=False)
        win = fluid.layers.data(name="window", shape=[batch, window],
                                dtype="int64", append_batch_size=False)
        positions = fluid.layers.data(
            name="positions", shape=[batch, window], dtype="int64",
            append_batch_size=False)

        tok64 = fluid.layers.reshape(
            x=fluid.layers.cast(tok, "int64"), shape=[batch, 1])
        _, rest = fluid.layers.split(win, num_or_sections=[1, window - 1],
                                     dim=1)
        new_window = fluid.layers.concat([rest, tok64], axis=1)

        x = fluid.layers.embedding(new_window,
                                   size=[vocab_size, d_model]) \
            + fluid.layers.embedding(positions, size=[window, d_model])
        for _ in range(n_layer):
            x = _block(x, n_head, d_model, d_ff, True, sp_axis, sp_mode)
        x = fluid.layers.layer_norm(x, begin_norm_axis=2)
        logits3 = fluid.layers.fc(input=x, size=vocab_size,
                                  num_flatten_dims=2)
        _, last = fluid.layers.split(
            logits3, num_or_sections=[window - 1, 1], dim=1)
        logits = fluid.layers.reshape(x=last, shape=[batch, vocab_size])
    return main, startup, logits, new_window


def build_transformer_cached_step_program(batch, max_len, vocab_size,
                                          n_layer=2, n_head=4,
                                          d_model=64, d_ff=None):
    """KV-cached decode step over a block of T >= 1 consecutive
    positions of every row: O(1) attention work per generated token
    (T = 1), and a prompt prefilled many positions at a time.

    Feeds: tok [batch, T] int32 (declared [batch, -1]: the open second
    axis is how `fluid.ProgramDecoder` knows the step takes a block),
    pos [batch] int64 (the slot the block's first token writes; per-row
    so beam expansion can repeat it — rows advance in lockstep),
    per-layer caches k_cache_i/v_cache_i [batch, n_head, max_len,
    d_head].  Fetches: logits [batch, vocab] of the block's LAST
    position alone (the only one whose continuation a caller chooses),
    pos + T, and the updated caches.  Returns (main, startup, logits,
    state_pairs) where state_pairs wires straight into
    `fluid.ProgramDecoder` (greedy, sampling and beam; pass
    max_positions=max_len so decoding past the cache extent errors
    instead of clamping).

    Parameter names match `build_transformer_program` of the same
    architecture (per-program name scopes; cache feeds and the
    cast/reshape glue create no parameters), so the trained scope
    drives this program directly — max_len must not exceed the trained
    sequence length (the position embedding's extent).  Types follow
    the scope's (bfloat16 weights give bfloat16 activations, logits and
    caches), except that the two embeddings are summed in float32 and
    stay so through the first block's LayerNorm and residual add.
    """
    if d_ff is None:
        d_ff = 4 * d_model
    d_head = d_model // n_head
    main = fluid.Program()
    startup = fluid.Program()
    with fluid.program_guard(main, startup):
        tok = fluid.layers.data(name="tok", shape=[batch, -1],
                                dtype="int32", append_batch_size=False)
        pos = fluid.layers.data(name="pos", shape=[-1], dtype="int64",
                                append_batch_size=False)
        caches = []
        for i in range(n_layer):
            caches.append((
                fluid.layers.data(
                    name="k_cache_%d" % i,
                    shape=[batch, n_head, max_len, d_head],
                    dtype="float32", append_batch_size=False),
                fluid.layers.data(
                    name="v_cache_%d" % i,
                    shape=[batch, n_head, max_len, d_head],
                    dtype="float32", append_batch_size=False)))

        # lookup_table squeezes a trailing size-1 ids dim (reference
        # convention), so [rows, T, 1] ids yield [rows, T, d]; 0 keeps
        # an axis as it comes (beam search feeds batch * beam rows)
        tok64 = fluid.layers.reshape(
            x=fluid.layers.cast(tok, "int64"), shape=[0, 0, 1])
        # rows move in lockstep: one run of wpe rows, pos .. pos + T - 1,
        # serves the whole batch.  T is read off the token feed: a one
        # a position of a row, counted before each for its offset and
        # all together for the advance
        ones = fluid.layers.fill_constant_batch_size_like(
            tok, shape=[1, 1], dtype="int64", value=1, input_dim_idx=1,
            output_dim_idx=1)
        pos_ids = fluid.layers.reshape(
            x=fluid.layers.cumsum(ones, axis=1, exclusive=True)
            + fluid.layers.reduce_max(pos), shape=[1, -1, 1])
        # wpe lookup is [1, T, d]; the residual add broadcasts it over
        # the batch.  The embeddings' sum is what every layer's input
        # is built on, so it stays float32 until the first block has
        # added to it: rounded to a scope's bfloat16 it carries that
        # rounding into all the layers (a one-token step never rounded
        # it: XLA fuses the sum into its readers there)
        wte = fluid.layers.embedding(tok64, size=[vocab_size, d_model])
        x = fluid.layers.cast(wte, "float32") + fluid.layers.cast(
            fluid.layers.embedding(pos_ids, size=[max_len, d_model]),
            "float32")

        state_pairs = []
        for i in range(n_layer):
            h = fluid.layers.layer_norm(x, begin_norm_axis=2)
            if i == 0:
                h = fluid.layers.cast(h, wte)   # the weights' type
            qkv = fluid.layers.fc(input=h, size=3 * d_model,
                                  num_flatten_dims=2)
            q, k, v = fluid.layers.split(qkv, num_or_sections=3, dim=-1)
            o, kc_out, vc_out = fluid.layers.cached_attention(
                q, k, v, caches[i][0], caches[i][1], pos,
                num_heads=n_head)
            state_pairs.append(("k_cache_%d" % i, kc_out.name))
            state_pairs.append(("v_cache_%d" % i, vc_out.name))
            o = fluid.layers.fc(input=o, size=d_model, num_flatten_dims=2)
            if i == 0:
                # the float32 sum ends here, in the weights' type (the
                # cast up is said, not left to promotion: a float8
                # scope's values promote to nothing)
                x = fluid.layers.cast(
                    x + fluid.layers.cast(o, "float32"), wte)
            else:
                x = x + o
            h = fluid.layers.layer_norm(x, begin_norm_axis=2)
            h = fluid.layers.fc(input=h, size=d_ff, num_flatten_dims=2,
                                act="relu")
            x = x + fluid.layers.fc(input=h, size=d_model,
                                    num_flatten_dims=2)

        # the head reads the block's last position alone: [batch, T,
        # vocab] logits would be most of a block's memory for T - 1
        # rows nobody reads
        x = fluid.layers.slice(x, axes=[1], starts=[-1],
                               ends=[2 ** 31 - 1])
        x = fluid.layers.layer_norm(x, begin_norm_axis=2)
        logits3 = fluid.layers.fc(input=x, size=vocab_size,
                                  num_flatten_dims=2)
        logits = fluid.layers.reshape(x=logits3, shape=[0, vocab_size])
        pos_out = pos + fluid.layers.reduce_sum(ones)
        state_pairs.append(("pos", pos_out.name))
    return main, startup, logits, state_pairs


def transformer_program_feeds(batch, seq_len, vocab_size, seed=0):
    rs = np.random.RandomState(seed)
    tokens = rs.randint(0, vocab_size, size=(batch, seq_len))
    targets = rs.randint(0, vocab_size, size=(batch, seq_len, 1))
    positions = np.broadcast_to(np.arange(seq_len), (batch, seq_len))
    return {"tokens": tokens.astype(np.int64),
            "positions": np.ascontiguousarray(positions).astype(np.int64),
            "targets": targets.astype(np.int64)}
