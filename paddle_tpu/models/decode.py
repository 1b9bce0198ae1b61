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
           "sample_decode", "block_diffusion_decode", "REMASKING"]

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

# Generation by diffusion over blocks (`block_diffusion_decode`): what a
# pass is under, inside `decode_steps`.  A pass that fixes positions of
# a block (inside it, the application that also stores the block before
# for good: a block's first pass), the rule that fixes them, and the
# last block's commit, an application of its own after the scan of
# blocks.
DENOISE_SCOPE = "diffusion_denoise"
FOLD_SCOPE = "diffusion_fold"
UNMASK_SCOPE = "diffusion_unmask"
COMMIT_SCOPE = "diffusion_commit"
REMASKING = ("low_confidence_static", "low_confidence_dynamic",
             "sequential")


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


def _prefill_blocks(step_fn, state, tokens, block):
    """The state after `tokens` [rows, n] went through a step that takes
    a block, `block` positions an application (a shorter one first for
    the remainder, the equal ones in one scan).  No logits are asked
    for: whatever the step computes for them alone is dead code."""
    rows, length = tokens.shape
    if length % block:
        state = step_fn(state, tokens[:, :length % block])[1]
    if length >= block:
        blocks = jnp.moveaxis(
            tokens[:, length % block:].reshape(rows, -1, block), 1, 0)
        state, _ = jax.lax.scan(
            lambda state, toks: (step_fn(state, toks)[1], None), state,
            blocks)
    return state


def _transfers(block_length, denoising_steps):
    """k_s [denoising_steps]: the positions a block's s-th denoising
    pass fixes at least, B // T and one more in the first B mod T."""
    base, more = divmod(block_length, denoising_steps)
    return jnp.asarray([base + (s < more) for s in range(denoising_steps)],
                       jnp.int32)


def _max_and_first(values):
    """(the largest, the first index that holds it) along the last axis
    of float32 `values`, in one variadic reduce: one read for the two."""
    axis = values.ndim - 1
    index = jax.lax.broadcasted_iota(jnp.int32, values.shape, axis)

    def larger(a, b):
        take = (a[0] > b[0]) | ((a[0] == b[0]) & (a[1] < b[1]))
        return jnp.where(take, a[0], b[0]), jnp.where(take, a[1], b[1])

    return jax.lax.reduce(
        (values, index), (jnp.float32(-jnp.inf), jnp.int32(0)), larger,
        (axis,))


def _unmask(logits, masked, k, remasking, threshold, temperature, top_k,
            key, mask_id):
    """(x0, conf, fix) [rows, B] of one denoising pass: each position's
    own prediction from its own row of `logits` (no shift), the
    probability it was predicted at, and which of the `masked` [rows, B]
    positions the pass fixes; `k` is the pass's k_s.  `logits` is the
    step's [rows, T, V], T = B or, from a block's first pass that
    carries a commit, 2B of which the last B are the block's own.  The
    mask token is no prediction: its logit counts for nothing, in the
    choice and in the probabilities (the published loop leaves it in,
    which a trained model never picks and seeded weights do once in a
    vocabulary's worth of positions: a position fixed to it would be
    masked again).

    The reductions over the vocabulary run on the logits as the head's
    product left them, [rows x T, V] in the step's own type, and widen
    to float32 inside themselves; their [rows x T] results are shaped
    afterwards.  ([rows, B, V] puts B positions in a tile of 8 or 16
    sublanes: a cast or a mask of that array is a relayout of the whole
    of it.)  Greedy, a 2B pass's every row is reduced and the last B
    results of a row are read: a slice of the logits in front would be
    an array of its own.  x0 is the first largest logit and conf =
    exp(max - logsumexp) = 1 / sum(exp(l - max)), so nothing is
    gathered; a sample's logit is, from its own B positions' rows."""
    rows, width = masked.shape
    vocab = logits.shape[-1]

    def struck(flat):
        return jnp.where(jnp.arange(vocab) == mask_id, NEG_INF,
                         flat.astype(jnp.float32))

    if temperature > 0:
        flat = struck(logits[:, -width:].reshape(-1, vocab)) / temperature
        if top_k:
            kth = jax.lax.top_k(flat, top_k)[0][..., -1:]
            flat = jnp.where(flat < kth, NEG_INF, flat)
        x0 = jax.random.categorical(key, flat, axis=-1)
        conf = jnp.exp(
            jnp.take_along_axis(flat, x0[..., None], axis=-1)[..., 0]
            - jax.nn.logsumexp(flat, axis=-1))
    else:
        flat = struck(logits.reshape(-1, vocab))
        most, x0 = _max_and_first(flat)
        conf = 1 / jnp.sum(jnp.exp(flat - most[:, None]), axis=-1)
    x0, conf = (x.reshape(rows, -1)[:, -width:] for x in (x0, conf))
    x0 = x0.astype(jnp.int32)
    conf = jnp.where(masked, conf, -jnp.inf)
    if remasking == "sequential":
        fix = masked & (jnp.cumsum(masked, axis=-1) <= k)
        return x0, conf, fix
    # the k masked positions of largest confidence (of equals the first)
    order = jax.lax.top_k(conf, width)[1]
    rank = jnp.argsort(order, axis=-1)
    fix = masked & (rank < k)
    if remasking == "low_confidence_dynamic":
        high = conf > threshold
        enough = jnp.sum(high, axis=-1, keepdims=True) >= k
        fix = jnp.where(enough, high, fix)
    return x0, conf, fix


def block_diffusion_decode(step_fn, init_state, prompt, gen_len,
                           block_length, denoising_steps, mask_id,
                           remasking="low_confidence_dynamic",
                           confidence_threshold=0.9, temperature=0.0,
                           top_k=0, rng=None, eos=None, hold=("pos",),
                           prefill_block=PREFILL_BLOCK):
    """Generation by diffusion over blocks (`block_diffusion_generate`
    of github.com/JetLM/SDAR's generate.py), lockstep rows, under jit.

    step_fn(state, tokens[rows, T]) -> (logits [rows, T, V], new_state)
    takes T consecutive positions of every row from the position `state`
    holds, T a multiple of `block_length` B, under a block-causal mask
    (position i sees every position up to the end of its own block of
    B), stores their keys and values and advances the position by T;
    row i of the logits predicts position i's own token.  `state` is a
    dict, and `hold` names its entries that count positions (the
    position: the step adds T to each), which the loop sets itself: a
    pass that stores nothing for good leaves them where they stood.

    The prompt's first B floor(P / B) positions are prefilled,
    `prefill_block` positions an application (cut to a multiple of B),
    and no logits are made for them.  Then block after block: the P mod
    B prompt tokens left over stand first in the first block and are
    never rewritten, every other entry starts as `mask_id`.  A
    *denoising pass* (`diffusion_denoise`) is the step over the block's
    tokens c; its state is handed on but for `hold`, so the slots it
    wrote are overwritten by the next pass and nothing ever reads them
    (no position advanced, and no later block exists yet).  The rule
    (`diffusion_unmask`, `_unmask`: its reductions over the vocabulary
    run on the logits flat, [rows x T, V] in the step's type) takes x0 =
    argmax (`temperature` 0) or a sample (temperature, `top_k`), conf =
    softmax(l)[x0] where c is masked, and
    with k_s = B // T + (s < B mod T) fixes, of the masked positions:
    "low_confidence_static" the k_s of largest conf;
    "low_confidence_dynamic" every one with conf > `confidence_threshold`
    if those are at least k_s, else the k_s largest; "sequential" the
    first k_s.  A block's *commit* is the step over its final tokens,
    kept: the cache holds what the final tokens give and the position
    stands B further.

    A commit rides on the next block's first denoising pass
    (`diffusion_fold`, inside `diffusion_denoise`): one application over
    the 2B positions [the block before's final tokens | c] from the
    block before's first position, whose state is kept with the position
    B further; the rule reduces its logits whole, [rows x 2B, V] as the
    head left them, and reads the last B of a row's 2B results (the
    first B's are computed and dropped: a slice of the logits in front
    of the rule would be an array of its own).  Under the
    mask that is the commit and the pass as two applications would give
    them (the block before sees the cache and itself; c sees the cache,
    the block before as just stored, and itself) and every weight is
    read once where twice.  The first generated block's "block before"
    is the prompt's last whole block, fed again at its own positions, so
    that every block starts on the same application; a prompt shorter
    than a block has none, and its first block starts on a plain pass.
    Only the last block's commit is an application of its own
    (`diffusion_commit`, after the scan of blocks; no logits read).  A
    block's first pass always runs (a fresh block holds a mask), so it
    stands before the loop of the others: "low_confidence_static" and
    "sequential" scan the `denoising_steps` - 1 others (a pass over a
    block with nothing masked fixes nothing), "low_confidence_dynamic"
    loops over at most that many and ends when no row has a masked
    position left (the published loop's test, over the batch), which
    only brings the next block's first pass, and the commit on it,
    sooner.

    Returns (tokens [rows, gen_len], lengths [rows], passes, fixed_pass
    [rows, gen_len] int32, the pass of its block (0 ..) that fixed a
    position, fixed_conf [rows, gen_len] float32, the confidence it was
    fixed at, state): pass s of a block was fed the final tokens where
    fixed_pass < s and `mask_id` elsewhere, so a call's whole trajectory
    can be replayed from these.  `passes` holds int32 scalars that count
    what was done, not how many applications it took: "denoise" the
    denoising passes and "commit" the blocks committed (one a block,
    wherever the commit ran); beside them "folded", the commits of those
    that rode on a denoising pass (every block's but the last), and
    "applications", the step's applications after the prefill (every
    denoising pass and the last commit: `blocks * denoising_steps + 1`
    where no block ends early, `blocks * (denoising_steps + 1)` with a
    commit pass a block).  `eos` only shapes `lengths` (the first eos
    and everything before it): nothing stops early."""
    if remasking not in REMASKING:
        raise ValueError("block_diffusion_decode: remasking %r is none of %s"
                         % (remasking, list(REMASKING)))
    if not 1 <= denoising_steps <= block_length:
        raise ValueError(
            "block_diffusion_decode: %d denoising steps for a block of %d "
            "(every pass fixes a position at least)"
            % (denoising_steps, block_length))
    prompt = jnp.asarray(prompt, jnp.int32)
    rows, length = prompt.shape
    whole = length // block_length * block_length
    left = length - whole
    blocks = -(-(left + gen_len) // block_length)
    transfers = _transfers(block_length, denoising_steps)
    dynamic = remasking == "low_confidence_dynamic"
    rng = jax.random.PRNGKey(0) if rng is None else rng

    def standing(new, state, further):
        """`new` with the position `further` positions past `state`'s."""
        return dict(new, **{name: state[name] + further for name in hold})

    def denoise(carry, s, before=None):
        """Pass s of a block; given `before`, the final tokens of the
        block before, the same application commits them."""
        state, c, at, conf_at, key = carry
        key, sub = jax.random.split(key)
        with jax.named_scope(DENOISE_SCOPE):
            if before is None:
                logits, new = step_fn(state, c)
            else:
                with jax.named_scope(FOLD_SCOPE):
                    logits, new = step_fn(
                        state, jnp.concatenate([before, c], axis=1))
            state = standing(new, state,
                             0 if before is None else block_length)
        with jax.named_scope(UNMASK_SCOPE):
            x0, conf, fix = _unmask(
                logits, c == mask_id, transfers[s], remasking,
                confidence_threshold, temperature, top_k, sub, mask_id)
            c = jnp.where(fix, x0, c)
            at = jnp.where(fix, s, at)
            conf_at = jnp.where(fix, conf, conf_at)
        return state, c, at, conf_at, key

    def one_block(carry, c):
        """A block's denoising passes: `carry` holds the state at the
        block before's first position and its final tokens (at the
        block's own and None where none is before), and leaves this
        block's so."""
        state, before, key, taken = carry
        loop = denoise((state, c, jnp.full(c.shape, -1, jnp.int32),
                        jnp.zeros(c.shape, jnp.float32), key), jnp.int32(0),
                       before)
        if dynamic:
            s, loop = jax.lax.while_loop(
                lambda it: (it[0] < denoising_steps)
                & jnp.any(it[1][1] == mask_id),
                lambda it: (it[0] + 1, denoise(it[1], it[0])),
                (jnp.int32(1), loop))
        else:
            loop, _ = jax.lax.scan(
                lambda loop, s: (denoise(loop, s), None), loop,
                jnp.arange(1, denoising_steps, dtype=jnp.int32))
            s = jnp.int32(denoising_steps)
        state, c, at, conf_at, key = loop
        return (state, c, key, taken + s), (c, at, conf_at)

    with jax.named_scope(PREFILL_SCOPE):
        telemetry.on_prefill_lowering("block", prefill_block)
        state = init_state if not whole else _prefill_blocks(
            step_fn, init_state, prompt[:, :whole],
            max(prefill_block // block_length, 1) * block_length)
    first = jnp.full((blocks, rows, block_length), mask_id, jnp.int32)
    first = first.at[0, :, :left].set(prompt[:, whole:])
    with jax.named_scope(STEPS_SCOPE):
        if whole:
            carry = (standing(state, state, -block_length),
                     prompt[:, whole - block_length:whole], rng,
                     jnp.int32(0))
            carry, fixed = jax.lax.scan(one_block, carry, first)
        else:
            carry, head = one_block((state, None, rng, jnp.int32(0)),
                                    first[0])
            carry, fixed = jax.lax.scan(one_block, carry, first[1:])
            fixed = jax.tree_util.tree_map(
                lambda one, others: jnp.concatenate([one[None], others]),
                head, fixed)
        state, last, _, denoised = carry
        with jax.named_scope(COMMIT_SCOPE):
            state = step_fn(state, last)[1]
    toks, at, conf_at = fixed

    def generated(x):   # [blocks, rows, B] -> [rows, gen_len]
        return jnp.moveaxis(x, 0, 1).reshape(rows, -1)[
            :, left:left + gen_len]

    toks = generated(toks)
    lengths = jnp.full((rows,), gen_len, jnp.int32)
    if eos is not None:
        lengths = jnp.where(jnp.any(toks == eos, axis=1),
                            jnp.argmax(toks == eos, axis=1) + 1, lengths)
    passes = {"denoise": denoised, "commit": jnp.int32(blocks),
              "folded": jnp.int32(blocks - 1), "applications": denoised + 1}
    return toks, lengths, passes, generated(at), generated(conf_at), state
