"""Dense (static-shape) autoregressive decoding under jit.

The performance-path counterpart of the LoD beam ops (reference:
beam_search_op.cc / beam_search_decode_op.cc and the v2
RecurrentGradientMachine::beamSearch generation loop,
RecurrentGradientMachine.h:307-309).  The reference's beam state is
dynamic (ragged candidate lists); on TPU the state is dense
[batch, beam] arrays scanned to max_len with lax.top_k — XLA compiles
one executable, no host bookkeeping.
"""

import functools

import jax
import jax.numpy as jnp

from ..obs import telemetry

__all__ = ["greedy_decode", "beam_search_decode_dense", "prefill",
           "sample_decode"]

NEG_INF = -1e30


# The positions of a row that one application of a block-taking step
# prefills, unless the step says otherwise (`prefill`'s `block`: the
# latent step's absorbed queries, heads x 576 values a token, size its
# own, models/latent_moe_program.py).  Swept on the chip (PERF.md section 5, gpt2m-decode): on the
# op's plain path the float32 scores of a block, [rows, heads, block,
# extent], size it, not the FLOPs.  Where `cached_attention` walks the
# live slots (kernels/gqa_decode.py: a whole-extent cache of 128-wide
# heads) no such array exists, and the same block is what keeps a
# key/value head's group of queries, [group * block, 128], and its
# scores over a block of slots resident in VMEM (1024 rows at a group of
# 8: exaone-turn-32k-ep16's question is one application).
PREFILL_BLOCK = 128

# The two parts of a compiled generation call, as `op_name` scopes in
# front of everything the part runs (`jit(<lambda>)/decode_steps/while/
# body/closed_call/<op type>/~<instance>/...`): the whole of `prefill`,
# and the scan of steps of each decoder below.  Names only (no kernel,
# layout or instruction changes); no "/" in either, which would cut the
# path.  The device trace's readers find a call's prefill and its
# decoding by them (benchmark/reduce/decoder_trace.py).
PREFILL_SCOPE = "decode_prefill"
STEPS_SCOPE = "decode_steps"


def prefill(step_fn, init_state, prompt, takes_block=False, block=None):
    """Feed a prompt through the step function, returning
    (state, first_token) where first_token [B] is the argmax of the
    last prompt position's logits — the natural continuation to seed
    the decode with.  prompt: int [B, P].

    By default step_fn(state, tokens[B]) takes one position, and the
    prompt is one scan of it.  Only the LAST logits ride the scan carry
    (the first step runs outside to shape the carry leaf), so prefill
    memory is O(B*V) regardless of prompt length.

    `takes_block`: step_fn(state, tokens[B, T]) takes T >= 1
    consecutive positions of every row and gives the logits of the
    last.  The prompt goes through in blocks of `block` positions
    (PREFILL_BLOCK unless the step states its own), the
    equal blocks inside one scan, a shorter block first for the
    remainder: every position is processed, in P / block
    applications instead of P."""
    with jax.named_scope(PREFILL_SCOPE):
        return _prefill(step_fn, init_state, prompt, takes_block,
                        block or PREFILL_BLOCK)


def _prefill(step_fn, init_state, prompt, takes_block, block):
    """`prefill`, inside its scope."""
    prompt = jnp.asarray(prompt, jnp.int32)
    if not takes_block:
        telemetry.on_prefill_lowering("step", 1)
        toks = jnp.moveaxis(prompt, 0, 1)  # [P, B]
        logits, state = step_fn(init_state, toks[0])

        def body(carry, tok):
            state, _ = carry
            logits, state = step_fn(state, tok)
            return (state, logits), None

        (state, logits), _ = jax.lax.scan(body, (state, logits), toks[1:])
        return state, jnp.argmax(logits, axis=-1).astype(jnp.int32)

    telemetry.on_prefill_lowering("block", block)
    rows, length = prompt.shape
    state, logits = init_state, None
    if length % block:
        logits, state = step_fn(state, prompt[:, :length % block])
    if length >= block:
        blocks = jnp.moveaxis(
            prompt[:, length % block:].reshape(rows, -1, block), 1, 0)
        # traced once: the scan's body and, where no block has given
        # any yet, the shape of the logits the scan carries
        step_fn, closed = jax.closure_convert(step_fn, state, blocks[0])
        if logits is None:
            like = jax.eval_shape(step_fn, state, blocks[0], *closed)[0]
            logits = jnp.zeros(like.shape, like.dtype)

        def body(carry, toks):
            logits, state = step_fn(carry[0], toks, *closed)
            return (state, logits), None

        (state, logits), _ = jax.lax.scan(body, (state, logits), blocks)
    return state, jnp.argmax(logits, axis=-1).astype(jnp.int32)


def greedy_decode(step_fn, init_state, bos, eos, max_len, batch_size,
                  with_state=False):
    """step_fn(state, tokens[B]) -> (logits [B,V], new_state).
    Returns (tokens [B, max_len], lengths [B]), and with `with_state`
    the state after the last step as a third.  `bos` may be a scalar
    or a per-row [B] array (e.g. prefill's first_token)."""

    def body(carry, _):
        state, tok, done = carry
        logits, state = step_fn(state, tok)
        nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        nxt = jnp.where(done, eos, nxt)
        done = done | (nxt == eos)
        return (state, nxt, done), nxt

    bos = jnp.asarray(bos, jnp.int32)
    tok0 = jnp.broadcast_to(bos, (batch_size,))
    # per-row seeds (prefill continuations) that are already eos emit
    # eos throughout; a SCALAR bos may deliberately equal eos (the
    # GPT-2 endoftext convention) and must still generate
    done0 = (tok0 == eos) if bos.ndim else \
        jnp.zeros((batch_size,), bool)
    with jax.named_scope(STEPS_SCOPE):
        (state, _, done), toks = jax.lax.scan(
            body, (init_state, tok0, done0), None, length=max_len)
    toks = jnp.moveaxis(toks, 0, 1)               # [B, L]
    lengths = jnp.argmax(toks == eos, axis=1) + 1
    lengths = jnp.where(jnp.any(toks == eos, axis=1), lengths, max_len)
    return (toks, lengths, state) if with_state else (toks, lengths)


def sample_decode(step_fn, init_state, bos, eos, max_len, batch_size,
                  rng, temperature=1.0, top_k=0):
    """Ancestral sampling under jit: per-step categorical draw from
    the (temperature-scaled, optionally top-k-truncated) logits.
    Returns (tokens [B, max_len], lengths [B]).  `rng` is a JAX PRNG
    key; `bos` may be scalar or per-row (prefill seed)."""

    def body(carry, _):
        state, tok, done, key = carry
        logits, state = step_fn(state, tok)
        logits = logits.astype(jnp.float32) / jnp.maximum(
            temperature, 1e-6)
        if top_k:
            kth = jax.lax.top_k(logits, top_k)[0][:, -1:]
            logits = jnp.where(logits < kth, NEG_INF, logits)
        key, sub = jax.random.split(key)
        nxt = jax.random.categorical(sub, logits, axis=-1) \
            .astype(jnp.int32)
        nxt = jnp.where(done, eos, nxt)
        done = done | (nxt == eos)
        return (state, nxt, done, key), nxt

    bos = jnp.asarray(bos, jnp.int32)
    tok0 = jnp.broadcast_to(bos, (batch_size,))
    done0 = (tok0 == eos) if bos.ndim else \
        jnp.zeros((batch_size,), bool)
    with jax.named_scope(STEPS_SCOPE):
        (_, _, done, _), toks = jax.lax.scan(
            body, (init_state, tok0, done0, rng), None, length=max_len)
    toks = jnp.moveaxis(toks, 0, 1)
    lengths = jnp.argmax(toks == eos, axis=1) + 1
    lengths = jnp.where(jnp.any(toks == eos, axis=1), lengths, max_len)
    return toks, lengths


def beam_search_decode_dense(step_fn, init_state, bos, eos, beam_size,
                             max_len, batch_size,
                             length_penalty=0.0):
    """Batched beam search, fully jittable.

    step_fn(state, tokens[N]) -> (logits [N,V], new_state) where N =
    batch*beam and every state leaf is [N, ...].  Returns
    (tokens [B, beam, max_len], scores [B, beam]) sorted best-first.
    """
    B, K = batch_size, beam_size

    def expand(t):
        return jnp.repeat(t, K, axis=0)

    state = jax.tree_util.tree_map(expand, init_state)
    tok = expand(jnp.broadcast_to(jnp.asarray(bos, jnp.int32), (B,)))
    # only beam 0 alive at t=0 so the first top-k doesn't pick K copies
    scores = jnp.tile(jnp.concatenate(
        [jnp.zeros((1,), jnp.float32),
         jnp.full((K - 1,), NEG_INF, jnp.float32)]), (B,))
    done = jnp.zeros((B * K,), bool)

    def body(carry, _):
        state, tok, scores, done = carry
        logits, new_state = step_fn(state, tok)
        V = logits.shape[-1]
        logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
        # finished beams: only eos continues, at no cost
        eos_only = jnp.full((V,), NEG_INF).at[eos].set(0.0)
        logp = jnp.where(done[:, None], eos_only[None, :], logp)
        total = scores[:, None] + logp                  # [B*K, V]
        total = total.reshape(B, K * V)
        top_scores, top_idx = jax.lax.top_k(total, K)    # [B, K]
        beam_idx = top_idx // V                          # within-batch beam
        tok_idx = (top_idx % V).astype(jnp.int32)
        flat_src = (jnp.arange(B)[:, None] * K + beam_idx).reshape(-1)

        state = jax.tree_util.tree_map(
            lambda t: t[flat_src], new_state)
        tok = tok_idx.reshape(-1)
        scores = top_scores.reshape(-1)
        done = done[flat_src] | (tok == eos)
        return (state, tok, scores, done), (tok_idx, beam_idx)

    with jax.named_scope(STEPS_SCOPE):
        (state, tok, scores, done), (toks, parents) = jax.lax.scan(
            body, (state, tok, scores, done), None, length=max_len)

    # backtrack through the per-step parent pointers (reference:
    # beam_search_decode_op PackAllSteps backtracking)
    def back(carry, step):
        beam = carry                                   # [B, K]
        tok_t, par_t = step
        cur_tok = jnp.take_along_axis(tok_t, beam, axis=1)
        prev_beam = jnp.take_along_axis(par_t, beam, axis=1)
        return prev_beam, cur_tok

    last_beam = jnp.tile(jnp.arange(K)[None, :], (B, 1))
    _, rev_toks = jax.lax.scan(back, last_beam, (toks, parents),
                               reverse=True)
    sequences = jnp.moveaxis(rev_toks, 0, 2)           # [B, K, L]
    final_scores = scores.reshape(B, K)
    if length_penalty:
        lengths = jnp.sum(jnp.cumsum(sequences == eos, axis=2) == 0,
                          axis=2) + 1
        final_scores = final_scores / (lengths.astype(jnp.float32)
                                       ** length_penalty)
    order = jnp.argsort(-final_scores, axis=1)
    sequences = jnp.take_along_axis(sequences, order[:, :, None], axis=1)
    final_scores = jnp.take_along_axis(final_scores, order, axis=1)
    return sequences, final_scores
