"""Plain float32 reference of SDAR-30B-A3B-Chat
(huggingface.co/JetLM/SDAR-30B-A3B-Chat, `model_type` `sdar_moe`), a
pipeline stage of it: the full forward of a whole sequence under the
block-causal mask in straightforward `jax.numpy`, no cache, no kernel,
every key/value head repeated for its group of query heads, every expert
applied densely to every token, highest matmul precision, nothing
imported from the program; and on top of it the family's generation loop
(`block_diffusion_generate` of github.com/JetLM/SDAR's generate.py).

The model.  Hidden 2048, every layer the same kind.  With x [seq,
hidden] the float32 residual stream, RMSNorms N with a learned scale
each (eps 1e-6), no bias anywhere, a block length B and b(i) = i // B:

    a = x + Attn_l(N_in(x))
    y = a + MoE_l(N_pre_mlp(a))

Attn_l, for u = N_in(x), 32 query heads and 4 key/value heads of 128:

    q = u W_q, k = u W_k, v = u W_v, split into heads
    q, k: RMSNorm over each head's 128 values, one learned [128] scale
          for q and one for k a layer
    q, k turned rotate-half at the token's position, theta 1e6
    query head j reads key/value head j // 8
    position i attends position j iff b(j) <= b(i): every position up to
    the end of its own block, the later ones of its block among them
    o_j = sum_s softmax_s(q_j . k_s / sqrt(128)) v_s;  Attn = [o_j] W_o

MoE_l (every layer, no shared expert; `intermediate_size` is unread):

    P = softmax(u W_r) over all 128 experts, float32
    e_j, j < 8: the largest of P;  w_j = P_{e_j} / sum_j P_{e_j}
    MoE(u) = sum_j w_j W_down,e_j (silu(W_gate,e_j u) * W_up,e_j u)

After the last layer z = N_f(x) W_head, an untied head; row i of z
predicts position i's own token (a masked position's: no shift).

Generation (`generate`; mask id m, prompt of P tokens, G to generate, T
denoising steps a block):

  1. the first B floor(P / B) prompt positions are stored as they are;
  2. block n = positions nB .. nB + B - 1, tokens c: the P mod B prompt
     tokens left over stand first in the first block and are never
     rewritten, every other entry starts as m;
  3. a denoising pass: l = the whole forward of (everything before the
     block, c), its last B rows; x0_i = argmax l_i, conf_i =
     softmax(l_i)[x0_i] where c_i = m, else -inf; with k_s = B // T + (s
     < B mod T): `low_confidence_static` fixes the k_s masked positions
     of largest conf, `low_confidence_dynamic` every masked position
     with conf > tau if those are at least k_s, else the k_s largest,
     `sequential` the first k_s masked positions;
  4. once the block holds no m it is committed: it joins "everything
     before" as its final tokens.

Departures from the published loop, each for the program's sake and each
the same there (models/decode.py `block_diffusion_decode`): greedy only
here (the program also samples); rows move in lockstep and a block is
committed when no row has an m left in it, which is the published test;
the mask token is no prediction (its logit counts for nothing: the
published loop leaves it in, a trained model never picks it, and seeded
weights would once in a vocabulary's worth of positions);
`low_confidence_static` and `sequential` always take T passes a block (a
pass over a block with nothing masked fixes nothing: the published loop
would have left a pass earlier where a prompt's leftover tokens fill
part of the first block, with the same tokens); no early stop at an eos;
a position that is not m is never rewritten (the published code guards
it with a `where` on the mask too).

`params`: {"embed" [vocab, hidden], "blocks": [{"input_norm", "wq"
[hidden, 32 * 128], "wk", "wv" [hidden, 4 * 128], "q_norm", "k_norm"
[128], "wo", "pre_mlp_norm", "router" [hidden, experts], "w_gate",
"w_up" [experts, hidden, width], "w_down" [experts, width, hidden]}],
"norm_f", "head" [hidden, vocab]}, matrices as [in, out].  `cfg` has the
source's keys.  `cfg["control"]` (a dict, absent in every configuration)
makes the reference wrong on purpose, for the checks that have to see
it: {"causal_in_block": true} masks causally inside a block (j <= i);
{"causal_prefill": P} masks the first P positions causally, as a prompt
prefilled by an autoregressive step would be; {"kv_dtype": "<type>"}
rounds every stored key and value to that type; {"no_commit": true} is
`replay`'s: the stored positions are what each block's *last denoising
pass* saw, not its final tokens.

`replay` and `trajectory` are what the benchmark's cell asks of it
(benchmark/reference/sdar_moe.py is a copy of this file): a layer's
parameters held one at a time, a served call's passes recomputed from
its own results.
"""

import math

import jax
import jax.numpy as jnp
import numpy as np

REMASKING = ("low_confidence_static", "low_confidence_dynamic",
             "sequential")


def _control(cfg, name, default):
    return cfg.get("control", {}).get(name, default)


def rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(
        jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps) * scale


def rope(x, positions, theta):
    """x [seq, heads, dim] turned at `positions` [seq] (rotate-half: x
    cos + rotate_half(x) sin, the two halves of a head paired)."""
    half = x.shape[-1] // 2
    inv_freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    angles = positions.astype(jnp.float32)[:, None, None] * inv_freq
    angles = jnp.concatenate([angles, angles], axis=-1)
    x1, x2 = x[..., :half], x[..., half:]
    return x * jnp.cos(angles) + jnp.concatenate([-x2, x1], axis=-1) \
        * jnp.sin(angles)


def visible(cfg, q_at, k_at, block_length):
    """[queries, keys] bool: query position i sees key position j iff
    b(j) <= b(i) (the controls: j <= i inside a block, or among the
    first `causal_prefill` positions)."""
    q_at, k_at = jnp.asarray(q_at)[:, None], jnp.asarray(k_at)[None, :]
    seen = k_at // block_length <= q_at // block_length
    causal = k_at <= q_at
    if _control(cfg, "causal_in_block", False):
        return causal
    prompt = _control(cfg, "causal_prefill", 0)
    return jnp.where(q_at < prompt, causal, seen)


def _stored(cfg, t):
    """A key or value as the cache keeps it: float32, or under the
    control `kv_dtype` rounded to that type's exponent and mantissa (an
    explicit rounding: the TPU's compiler drops a cast to a narrower type
    that is followed by a cast back up)."""
    narrow = _control(cfg, "kv_dtype", None)
    if narrow is None:
        return t
    bits = jnp.finfo(jnp.dtype(narrow))
    return jax.lax.reduce_precision(t, exponent_bits=bits.nexp,
                                    mantissa_bits=bits.nmant)


def cached(cfg, block, h, positions):
    """(k [seq, kv heads, dim] normed and turned, v [seq, kv heads,
    dim]) of h [seq, hidden] at `positions` [seq]: what the two caches
    of the layer hold."""
    dim, kv_heads = cfg["head_dim"], cfg["num_key_value_heads"]
    seq = h.shape[0]
    k = rms_norm((h @ block["wk"]).reshape(seq, kv_heads, dim),
                 block["k_norm"], cfg["rms_norm_eps"])
    k = rope(k, positions, float(cfg["rope_theta"]))
    return _stored(cfg, k), \
        _stored(cfg, (h @ block["wv"]).reshape(seq, kv_heads, dim))


def attend(cfg, block, h, positions, k, v, mask):
    """Attention of the queries h [queries, hidden] at `positions`
    [queries] over the keys k and values v [keys, kv heads, dim] under
    `mask` [queries, keys], through W_o."""
    heads, dim = cfg["num_attention_heads"], cfg["head_dim"]
    q = rms_norm((h @ block["wq"]).reshape(-1, heads, dim),
                 block["q_norm"], cfg["rms_norm_eps"])
    q = rope(q, positions, float(cfg["rope_theta"]))
    group = heads // k.shape[1]
    k_all, v_all = (jnp.repeat(t, group, axis=1) for t in (k, v))
    scores = jnp.einsum("qhd,khd->hqk", q, k_all) / math.sqrt(dim)
    scores = jnp.where(mask[None], scores, -jnp.inf)
    out = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(scores, axis=-1),
                     v_all)
    return out.reshape(out.shape[0], -1) @ block["wo"]


def routed(cfg, block, u):
    """MoE(u) for u [tokens, hidden]: every expert applied to every
    token, one after another (a scan), weighted by the token's weight of
    it, 0 where it is not among the token's chosen."""
    probs = jax.nn.softmax(u @ block["router"], axis=-1)
    top, indices = jax.lax.top_k(probs, cfg["num_experts_per_tok"])
    if cfg["norm_topk_prob"]:
        top = top / jnp.sum(top, axis=-1, keepdims=True)
    hot = indices[..., None] == jnp.arange(probs.shape[-1])
    weights = jnp.sum(jnp.where(hot, top[..., None], 0.0), axis=1)

    def add_expert(m, expert):
        w_gate, w_up, w_down, weight = expert
        hidden = jax.nn.silu(u @ w_gate) * (u @ w_up)
        return m + weight[:, None] * (hidden @ w_down), None

    m, _ = jax.lax.scan(add_expert, jnp.zeros_like(u), (
        block["w_gate"], block["w_up"], block["w_down"], weights.T))
    return m


def attention_half(cfg, block, x, positions, k_before, v_before, mask):
    """a = x + Attn(N(x)) for the tokens x [n, hidden] at `positions`
    [n], which attend the stored `k_before`, `v_before` [m, kv heads,
    dim] and their own keys and values under `mask` [n, m + n]: (a, the
    n tokens' own keys, their values)."""
    h = rms_norm(x, block["input_norm"], cfg["rms_norm_eps"])
    k, v = cached(cfg, block, h, positions)
    return x + attend(cfg, block, h, positions,
                      jnp.concatenate([k_before, k]),
                      jnp.concatenate([v_before, v]), mask), k, v


def expert_half(cfg, block, a):
    """y = a + MoE(N(a)) for a [tokens, hidden]."""
    return a + routed(cfg, block, rms_norm(a, block["pre_mlp_norm"],
                                           cfg["rms_norm_eps"]))


def layer(cfg, block, x, positions, k_before, v_before, mask):
    """One decoder layer (`attention_half`, then `expert_half`): (its
    output [n, hidden], the n tokens' own keys, their values)."""
    a, k, v = attention_half(cfg, block, x, positions, k_before, v_before,
                             mask)
    return expert_half(cfg, block, a), k, v


def _f32(tree):
    return jax.tree_util.tree_map(lambda a: jnp.asarray(a, jnp.float32),
                                  tree)


def forward(cfg, params, tokens, block_length):
    """{"logits" [batch, seq, vocab], "keys", "values": [L] x [batch,
    seq, kv heads, dim] what the caches would hold} for `tokens` [batch,
    seq] from position 0 under the block-causal mask."""
    params = _f32(params)
    tokens = np.asarray(tokens)
    seq = tokens.shape[1]
    at = jnp.arange(seq)
    mask = visible(cfg, at, at, block_length)
    none = jnp.zeros((0, cfg["num_key_value_heads"], cfg["head_dim"]))
    out = {"keys": [], "values": []}
    with jax.default_matmul_precision("highest"):
        xs = [params["embed"][jnp.asarray(row)] for row in tokens]
        for block in params["blocks"]:
            made = [layer(cfg, block, x, at, none, none, mask) for x in xs]
            xs = [m[0] for m in made]
            out["keys"].append(jnp.stack([m[1] for m in made]))
            out["values"].append(jnp.stack([m[2] for m in made]))
        out["logits"] = rms_norm(jnp.stack(xs), params["norm_f"],
                                 cfg["rms_norm_eps"]) @ params["head"]
    return out


def transfers(block_length, denoising_steps):
    """k_s of a block's passes: B // T, one more in the first B mod T."""
    base, more = divmod(block_length, denoising_steps)
    return [base + (s < more) for s in range(denoising_steps)]


def unmasked(logits, mask_id):
    """`logits` [..., vocab] with the mask token's at -inf: it is no
    prediction, in the choice or in the probabilities."""
    return jnp.where(jnp.arange(logits.shape[-1]) == mask_id, -jnp.inf,
                     jnp.asarray(logits, jnp.float32))


def fixes(conf, masked, k, remasking, threshold):
    """Which of one row's `masked` positions [B] a pass fixes, from
    their confidences `conf` [B] float (rule 3; of equal confidences the
    earlier position)."""
    conf = np.where(masked, conf, -np.inf)
    if remasking == "sequential":
        return masked & (np.cumsum(masked) <= k)
    order = np.argsort(-conf, kind="stable")
    fix = np.zeros_like(masked)
    fix[order[:k]] = True
    fix &= masked
    if remasking == "low_confidence_dynamic":
        high = conf > threshold
        if high.sum() >= k:
            fix = high
    return fix


def generate(cfg, params, prompt, gen_len, block_length, denoising_steps,
             mask_id, remasking="low_confidence_dynamic",
             confidence_threshold=0.9):
    """The generation loop over whole forwards, greedy: every pass is
    `forward` of (the prompt's whole blocks, the committed blocks, the
    current block), no cache.  Returns {"tokens" [rows, gen_len],
    "fixed_pass" [rows, gen_len] the pass of its block that fixed a
    position, "fixed_conf" [rows, gen_len] the confidence it was fixed
    at, "passes": {"denoise", "commit"}, "inputs": [(block, pass, c
    [rows, B])] every denoising pass's input, "logits": [[rows, B,
    vocab]] its logits}."""
    if remasking not in REMASKING:
        raise ValueError("remasking %r is none of %s"
                         % (remasking, list(REMASKING)))
    prompt = np.asarray(prompt)
    rows, length = prompt.shape
    whole = length // block_length * block_length
    left = length - whole
    blocks = -(-(left + gen_len) // block_length)
    k_of = transfers(block_length, denoising_steps)
    dynamic = remasking == "low_confidence_dynamic"
    before = prompt[:, :whole]
    at = np.full((rows, blocks * block_length), -1, np.int32)
    conf_at = np.zeros((rows, blocks * block_length), np.float32)
    inputs, logits_of, denoised = [], [], 0
    for n in range(blocks):
        c = np.full((rows, block_length), mask_id, np.int32)
        if n == 0:
            c[:, :left] = prompt[:, whole:]
        here = slice(n * block_length, (n + 1) * block_length)
        for s in range(denoising_steps):
            if dynamic and not (c == mask_id).any():
                break
            logits = np.asarray(forward(
                cfg, params, np.concatenate([before, c], axis=1),
                block_length)["logits"][:, -block_length:])
            inputs.append((n, s, c.copy()))
            logits_of.append(logits)
            denoised += 1
            logits = np.asarray(unmasked(logits, mask_id))
            x0 = logits.argmax(-1)
            shifted = logits - logits.max(-1, keepdims=True)
            conf = 1.0 / np.exp(shifted).sum(-1)    # softmax(l)[argmax]
            for row in range(rows):
                fix = fixes(conf[row], c[row] == mask_id, k_of[s],
                            remasking, confidence_threshold)
                c[row, fix] = x0[row, fix]
                at[row, here][fix] = s
                conf_at[row, here][fix] = conf[row, fix]
        before = np.concatenate([before, c], axis=1)    # the commit
    cut = slice(left, left + gen_len)
    return {"tokens": before[:, whole:][:, cut], "fixed_pass": at[:, cut],
            "fixed_conf": conf_at[:, cut],
            "passes": {"denoise": denoised, "commit": blocks},
            "inputs": inputs, "logits": logits_of}


def pass_inputs(tokens, fixed_pass, whole, left, block_length, mask_id,
                blocks, passes=None):
    """What a served call fed its denoising passes, from its results:
    {(block n, pass s): c [rows, B]}, the block's final tokens where
    they were fixed before pass s (the prompt's leftover tokens always)
    and `mask_id` elsewhere.  `tokens` [rows, >= whole + blocks' span]
    are prompt and generated tokens, `fixed_pass` [rows, gen_len] as the
    decoder returns it; `blocks`: the generated blocks wanted (counted
    from the first generated one), `passes`: a block's passes wanted
    (default: every pass that fixed something)."""
    tokens, fixed_pass = np.asarray(tokens), np.asarray(fixed_pass)
    rows = tokens.shape[0]
    out = {}
    for n in blocks:
        final = tokens[:, whole + n * block_length:
                       whole + (n + 1) * block_length]
        at = np.full((rows, block_length), np.iinfo(np.int32).max, np.int32)
        lo, hi = n * block_length - left, (n + 1) * block_length - left
        span = fixed_pass[:, max(lo, 0):max(hi, 0)]
        at[:, max(-lo, 0):max(-lo, 0) + span.shape[1]] = span
        if n == 0:
            at[:, :left] = -1      # the prompt's: never masked
        last = int(at[at < np.iinfo(np.int32).max].max())
        for s in range(last + 1) if passes is None else passes:
            out[n, s] = np.where(at < s, final, mask_id).astype(np.int32)
    return out


def fixed_by(keys, rows, final, fixed_pass, fixed_conf, whole, length,
             block_length):
    """What the program's passes `keys` = [(block n, pass s)] fixed, a
    row of `rows` after a row a pass, as `trajectory` asks for it:
    (served [passes * rows, B] the blocks' final tokens, fixed [passes *
    rows, B] bool the positions pass s fixed, conf the confidence it
    fixed them at, 1 elsewhere).  `final` [rows, >= the blocks' span]
    are prompt (`length` tokens, `whole` of them in whole blocks) and
    generated tokens, `fixed_pass` and `fixed_conf` [rows, gen_len] as
    the decoder returns them: a block's positions before the prompt's
    end or past the generated length are nobody's."""
    gen = fixed_pass.shape[1]
    at = np.asarray([whole + n * block_length - length for n, _ in keys
                     for _ in range(rows)])[:, None] \
        + np.arange(block_length)
    pick = np.clip(at, 0, gen - 1)
    row_of = np.tile(np.arange(rows), len(keys))[:, None]
    s_of = np.repeat([s for _, s in keys], rows)[:, None]
    fixed = (at >= 0) & (at < gen) & (fixed_pass[row_of, pick] == s_of)
    served = final[row_of, np.clip(at + length, 0, final.shape[1] - 1)]
    return served, fixed, np.where(fixed, fixed_conf[row_of, pick], 1.0)


def replay(cfg, ends, block_of, tokens, block_length, wanted, whole=0):
    """A served call's passes recomputed, a layer's parameters held one
    at a time: (logits [passes, B, vocab] float32, keys, values of the
    **first** layer [rows, seq, kv heads, dim]).

    `tokens` [rows, seq] are the final sequences (prompt and generated
    tokens, whole blocks), `wanted` = [(row, first position, c [B])] the
    passes to recompute: the B tokens `c` at positions first .. first +
    B - 1 of row `row`, behind that row's positions before `first`.
    One whole forward over each final sequence gives every layer's
    float32 keys and values; a pass is its B queries over that forward's
    own keys and values of the earlier positions and its own B.  Under
    the mask no earlier position sees the block, so this is the whole
    forward of (the earlier final tokens, c), not an approximation of
    it (tests/test_sdar_program.py holds the two equal).

    Under the control `no_commit` the sequences stored are what each
    block's last denoising pass saw and not its final tokens (`tokens`
    then holds those: the caller makes them with `pass_inputs`)."""
    ends = _f32(ends)
    tokens = np.asarray(tokens)
    rows, seq = tokens.shape
    at = jnp.arange(seq)
    mask = visible(cfg, at, at, block_length)
    none = jnp.zeros((0, cfg["num_key_value_heads"], cfg["head_dim"]))
    row_of = jnp.asarray([w[0] for w in wanted], jnp.int32)
    first_of = jnp.asarray([w[1] for w in wanted], jnp.int32)
    fed = jnp.asarray(np.stack([w[2] for w in wanted]), jnp.int32)
    width = fed.shape[1]

    @jax.jit
    def whole_layer(block, x):
        with jax.default_matmul_precision("highest"):
            return layer(cfg, block, x, at, none, none, mask)

    @jax.jit
    def pass_layer(block, p, keys, values):
        def one(p, row, first):
            here = first + jnp.arange(width)
            # the stored positions before the block, then the block's
            seen = jnp.concatenate([
                visible(cfg, here, at, block_length) & (at < first)[None],
                visible(cfg, here, here, block_length)], axis=1)
            return attention_half(cfg, block, p, here, keys[row],
                                  values[row], seen)[0]

        with jax.default_matmul_precision("highest"):
            # a pass's attention by itself; every pass's tokens through
            # the experts at once
            a = jax.lax.map(lambda a: one(*a), (p, row_of, first_of))
            return expert_half(cfg, block, a.reshape(-1, a.shape[-1])) \
                .reshape(a.shape)

    @jax.jit
    def head(ends, p):
        with jax.default_matmul_precision("highest"):
            return rms_norm(p, ends["norm_f"], cfg["rms_norm_eps"]) \
                @ ends["head"]

    xs = [ends["embed"][jnp.asarray(row)] for row in tokens]
    p = ends["embed"][fed]
    first_kv = None
    for i in range(cfg["num_hidden_layers"]):
        block = _f32(block_of(i))
        made = [whole_layer(block, x) for x in xs]
        xs = [m[0] for m in made]
        keys, values = (jnp.stack([m[j] for m in made]) for j in (1, 2))
        if i == 0:
            first_kv = np.asarray(keys), np.asarray(values)
        p = pass_layer(block, p, keys, values)
        del block, made, keys, values
    return head(ends, p), first_kv[0], first_kv[1]


def off(got, want):
    """Root mean square of got - want over want's."""
    got, want = (np.asarray(a, np.float64) for a in (got, want))
    size = np.mean(np.square(want))
    diff = np.mean(np.square(got - want))
    if size == 0.0:
        return 0.0 if diff == 0.0 else float("inf")
    return float((diff / size) ** 0.5)


def trajectory(logits, fed, served, fixed, conf, mask_id):
    """What `correct` compares of recomputed passes: `logits` [passes,
    B, vocab] the reference's for the inputs `fed` [passes, B], `served`
    [passes, B] the block's final tokens, `fixed` [passes, B] bool the
    positions the program's pass fixed and `conf` [passes, B] the
    confidence it fixed them at.  {"gap_mean": by how much the
    reference's logit of a fixed token lies under the reference's best
    at the positions the pass fixed, "not_first_share": the share of
    them where it is not the best, "conf_off": the mean of |ln program's
    confidence - ln the reference's probability of the same token|
    there, "other_position_share": the share of passes that fixed one
    position which is not the masked position the reference ranks first
    for the same input, "fixed": the positions compared}."""
    z = unmasked(logits, mask_id)
    served, fixed = jnp.asarray(served), jnp.asarray(fixed)
    best = jnp.max(z, axis=-1)
    picked = jnp.take_along_axis(z, served[..., None], axis=-1)[..., 0]
    lse = jax.nn.logsumexp(z, axis=-1)
    gaps = np.asarray(best - picked, np.float64)[np.asarray(fixed)]
    ln_off = np.abs(np.log(np.asarray(conf, np.float64))
                    - np.asarray(picked - lse, np.float64))[
                        np.asarray(fixed)]
    masked = np.asarray(fed) == mask_id
    ranked = np.where(masked, np.asarray(best - lse), -np.inf).argmax(-1)
    single = np.asarray(fixed).sum(-1) == 1
    other = np.asarray(fixed).argmax(-1) != ranked
    return {"gap_mean": float(gaps.mean()), "gap_max": float(gaps.max()),
            "not_first_share": float((gaps > 0).mean()),
            "conf_off": float(ln_off.mean()),
            "other_position_share": float(other[single].mean())
            if single.any() else 0.0,
            "fixed": int(gaps.size)}
