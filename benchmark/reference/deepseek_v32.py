"""What the session cell's served tokens are held to: the plain float32
reference of one chip's share of DeepSeek-V3.2, a copy of
paddle_tpu/models/reference/deepseek_v32.py's equations (see there: the
unabsorbed full-sequence forward, per layer the dense index scores, the
causal top-`index_topk` a query as a boolean mask, masked full attention
with every head's keys and values made from the latents, no cache and no
gather, the group-limited router with its selection bias, every held
expert applied densely to every token, highest matmul precision, nothing
from the program; the departures from the release are listed there),
applied layer by layer, a sequence at a time, over blocks of queries: at
16,384 positions one sequence's [heads, T, T] scores would be 137 GB, so
a layer keeps every position's keys and values (2.1 GB) and takes its
queries `query_block` at a time, and an expert layer is 3.8 GB in
float32, so `gaps` and `session` ask their caller for one layer's
parameters at a time and let go of them before the next.

Three things are made here: `session`, the caches a decode-pool chip is
handed (every layer's `c | r` and `k^I` of a document, which a prefill
pool would have computed); `gaps`, by how much the reference's logit of
each served token lies below the reference's best at that position (0
where they agree: with seeded weights the top two lie close often enough
that rounding picks the other one now and then, so the tokens themselves
are not compared); and beside the gaps, for the call's last step, what
the program's chooser and its attention over the chosen set are held to
(`layer`'s `last`), and `held_part_off` for its held experts.
"""

import math

import jax
import jax.numpy as jnp


def rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(
        jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps) * scale


def layer_norm(x, scale, bias, eps):
    centred = x - jnp.mean(x, axis=-1, keepdims=True)
    return centred * jax.lax.rsqrt(
        jnp.mean(jnp.square(centred), axis=-1, keepdims=True) + eps) \
        * scale + bias


def yarn_inv_freq(cfg):
    """[qk_rope_head_dim / 2] float32: YaRN's blended frequencies."""
    dim, theta = cfg["qk_rope_head_dim"], float(cfg["rope_theta"])
    scaling = cfg["rope_scaling"]
    original = scaling["original_max_position_embeddings"]

    def corr(turns):
        return dim * math.log(original / (turns * 2 * math.pi)) \
            / (2 * math.log(theta))

    lo = max(math.floor(corr(scaling["beta_fast"])), 0)
    hi = min(math.ceil(corr(scaling["beta_slow"])), dim - 1)
    i = jnp.arange(dim // 2, dtype=jnp.float32)
    freq = theta ** (-2.0 * i / dim)
    ramp = jnp.clip((i - lo) / max(hi - lo, 0.001), 0.0, 1.0)
    return freq / scaling["factor"] * ramp + freq * (1.0 - ramp)


def softmax_scale(cfg):
    scaling = cfg["rope_scaling"]
    mscale = 0.1 * scaling["mscale_all_dim"] * math.log(scaling["factor"]) \
        + 1.0
    return (cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]) ** -0.5 \
        * mscale * mscale


def rope(x, positions, inv_freq):
    """x [seq, heads, dim] with the first 2 * len(inv_freq) values of
    every head turned at `positions` [seq] (rotate-half), the rest as
    they are."""
    turned = 2 * inv_freq.shape[0]
    angles = positions[:, None, None].astype(jnp.float32) * inv_freq
    angles = jnp.concatenate([angles, angles], axis=-1)
    part = x[..., :turned]
    x1, x2 = part[..., :turned // 2], part[..., turned // 2:]
    rotated = jnp.concatenate([-x2, x1], axis=-1)
    return jnp.concatenate(
        [part * jnp.cos(angles) + rotated * jnp.sin(angles),
         x[..., turned:]], axis=-1)


def gated(u, w_in, w_out):
    gate, up = jnp.split(u @ w_in, 2, axis=-1)
    return (jax.nn.silu(gate) * up) @ w_out


def cached(cfg, block, h, positions):
    """(c_q [seq, q_rank], [c | r] [seq, latent + rope], k^I [seq, dim])
    of h [seq, hidden]: the normed query latent, and what the two caches
    of the layer hold."""
    eps, latent = cfg["rms_norm_eps"], cfg["kv_lora_rank"]
    inv_freq = yarn_inv_freq(cfg)
    c_q = rms_norm(h @ block["w_dq"], block["q_norm"], eps)
    ckv = h @ block["w_dkv"]
    c = rms_norm(ckv[..., :latent], block["kv_norm"], eps)
    r = rope(ckv[..., latent:][:, None, :], positions, inv_freq)[:, 0]
    k_i = layer_norm(h @ block["w_ik"], block["ik_norm"],
                     block["ik_norm_b"], eps)
    k_i = rope(k_i[:, None, :], positions, inv_freq)[:, 0]
    return c_q, jnp.concatenate([c, r], axis=-1), k_i


def index_scores(cfg, block, h, c_q, positions, k_i):
    """I [queries, keys] = sum_j w_j relu(q^I_j . k^I) for the queries h,
    c_q at `positions`, over all the keys `k_i` (no mask)."""
    heads, dim = cfg["index_n_heads"], cfg["index_head_dim"]
    q = rope((c_q @ block["w_iq"]).reshape(-1, heads, dim), positions,
             yarn_inv_freq(cfg))
    w = (h @ block["w_iw"]) * heads ** -0.5 * dim ** -0.5
    return jnp.einsum("qh,qhs->qs", w,
                      jax.nn.relu(jnp.einsum("qhd,sd->qhs", q, k_i)))


def choose(scores, top_k, q_positions):
    """The boolean mask [queries, keys] of the slots each query attends:
    of the keys s <= its position, the min(top_k, position + 1) with the
    largest score."""
    keys = scores.shape[-1]
    causal = jnp.arange(keys)[None, :] <= q_positions[:, None]
    live = jnp.where(causal, scores, -jnp.inf)
    kth = jax.lax.top_k(live, min(top_k, keys))[0][..., -1:]
    return causal & (live >= kth)


def attend(cfg, block, c_q, positions, k_nope, r, v, mask):
    """MLA of the queries c_q at `positions` over the keys [k_nope | r]
    and values v of every position, under `mask` [queries, keys]."""
    heads = cfg["num_attention_heads"]
    q_nope = (c_q @ block["w_uq_nope"]).reshape(-1, heads,
                                                k_nope.shape[-1])
    q_rope = rope((c_q @ block["w_uq_rope"]).reshape(-1, heads,
                                                     r.shape[-1]),
                  positions, yarn_inv_freq(cfg))
    scores = (jnp.einsum("qhd,khd->hqk", q_nope, k_nope)
              + jnp.einsum("qhd,kd->hqk", q_rope, r)) * softmax_scale(cfg)
    scores = jnp.where(mask[None], scores, -jnp.inf)
    out = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(scores, axis=-1), v)
    return out.reshape(out.shape[0], -1) @ block["wo"]


def group_limited(cfg, choice):
    """`choice` [tokens, experts] with -inf on the experts outside each
    token's `topk_group` best of `n_group` groups of consecutive
    experts; a group scores the sum of its two largest entries."""
    groups, kept = cfg["n_group"], cfg["topk_group"]
    n, experts = choice.shape
    grouped = choice.reshape(n, groups, experts // groups)
    group_score = jnp.sum(jax.lax.top_k(grouped, 2)[0], axis=-1)
    best = jax.lax.top_k(group_score, kept)[1]
    keep = jnp.any(best[:, :, None] == jnp.arange(groups), axis=1)
    return jnp.where(keep[:, :, None], grouped, -jnp.inf).reshape(n, experts)


def route(cfg, block, u, indices=None):
    """(weights [tokens, scored], indices [tokens, top_k]) of u [tokens,
    hidden]: a token's weight of each scored expert, 0 where it is not
    among its chosen (the reference's own choice, by s + b inside the
    kept groups, or `indices` where a caller hands it a routing); the
    weights read s."""
    scores = jax.nn.sigmoid(u @ block["router"])
    if indices is None:
        choice = group_limited(cfg, scores + block["router_bias"])
        indices = jax.lax.top_k(choice, cfg["num_experts_per_tok"])[1]
    top = jnp.take_along_axis(scores, indices, axis=1)
    if cfg["norm_topk_prob"]:
        top = top / (jnp.sum(top, axis=-1, keepdims=True) + 1e-20)
    top = top * cfg["routed_scaling_factor"]
    hot = indices[..., None] == jnp.arange(scores.shape[-1])
    return jnp.sum(jnp.where(hot, top[..., None], 0.0), axis=1), indices


def routed(cfg, block, u, first=0, indices=None):
    """The held experts' part of the routed sum for u [tokens, hidden]:
    every held expert applied to every token, one after another (a
    scan), weighted by the token's weight of it."""
    weights, indices = route(cfg, block, u, indices)
    count = block["w_gate"].shape[0]
    held = jax.lax.dynamic_slice_in_dim(weights, first, count, axis=1)

    def add_expert(m, expert):
        w_gate, w_up, w_down, weight = expert
        hidden = jax.nn.silu(u @ w_gate) * (u @ w_up)
        return m + weight[:, None] * (hidden @ w_down), None

    m, _ = jax.lax.scan(add_expert, jnp.zeros_like(u), (
        block["w_gate"], block["w_up"], block["w_down"], held.T))
    return m, indices


def feed_forward(cfg, block, u, first=0):
    """F(u) for u [tokens, hidden]."""
    if "ffn_in" in block:
        return gated(u, block["ffn_in"], block["ffn_out"])
    return routed(cfg, block, u, first)[0] \
        + gated(u, block["shared_in"], block["shared_out"])


def _blocks(x, size):
    """x [n, ...] as [n / size, size, ...]."""
    return x.reshape((x.shape[0] // size, size) + x.shape[1:])


def layer(cfg, block, x, first, query_block, last=None, start=0):
    """(y, [c | r], k^I, what `last` asks for) of one decoder layer over
    one sequence x [seq, hidden], seq - start a multiple of `query_block`.

    `start`: y is wanted from that position on only, [seq - start,
    hidden].  A position's output reads nothing after it, so a caller
    that has kept the layers' inputs of a prefix (`session`'s `keep`)
    continues from there: every position's keys, values and cache
    entries are made as before, the attention and the feed-forward run
    for the queries from `start` on.

    `last` = (at, h [hidden], selected [top_k] int32, live): the
    position of a decode step, the normed input the program's attention
    sub-layer had there, the slots its chooser picked and how many of
    them are live.  The fourth result is then what the reference makes
    of that same input at that position (its own float32 weights and
    arithmetic, its own caches of the positions before, the slot `at`
    itself made from `h`): (`shared`: how many of the min(top_k, at + 1)
    slots it would choose are among the program's, as a share; `attn`:
    the attention sub-layer's output [hidden] over the program's set),
    else None.  No upstream layer's drift is in either: both judge what
    this layer's chooser and attention did with what they were given."""
    eps, latent = cfg["rms_norm_eps"], cfg["kv_lora_rank"]
    heads, top_k = cfg["num_attention_heads"], cfg["index_topk"]
    seq = x.shape[0]
    positions = jnp.arange(seq)
    h = rms_norm(x, block["input_norm"], eps)
    c_q, cr, k_i = cached(cfg, block, h, positions)
    c, r = cr[:, :latent], cr[:, latent:]
    k_nope = (c @ block["w_uk"]).reshape(seq, heads, -1)
    v = (c @ block["w_uv"]).reshape(seq, heads, -1)

    def some_queries(part):
        h_b, c_q_b, at = part
        mask = choose(index_scores(cfg, block, h_b, c_q_b, at, k_i), top_k,
                      at)
        return attend(cfg, block, c_q_b, at, k_nope, r, v, mask)

    o = jax.lax.map(some_queries, tuple(
        _blocks(a[start:], query_block) for a in (h, c_q, positions)))
    a = x[start:] + o.reshape(seq - start, -1)
    u = rms_norm(a, block["pre_mlp_norm"], eps)
    tokens = math.gcd(seq - start, 2048)
    f = jax.lax.map(lambda part: feed_forward(cfg, block, part, first),
                    _blocks(u, tokens))
    found = None
    if last is not None:
        at, h_1, selected, live = last
        one, h_1 = jnp.reshape(at, (1,)), h_1[None]
        c_q_1, cr_1, k_i_1 = cached(cfg, block, h_1, one)
        c_1 = cr_1[:, :latent]
        put = jax.lax.dynamic_update_slice_in_dim
        own = choose(index_scores(cfg, block, h_1, c_q_1, one,
                                  put(k_i, k_i_1, at, 0)), top_k, one)[0]
        handed = jnp.zeros((seq,), bool).at[
            jnp.where(jnp.arange(selected.shape[0]) < live, selected,
                      seq)].set(True, mode="drop")
        found = (jnp.sum(own & handed) / jnp.sum(own), attend(
            cfg, block, c_q_1, one,
            put(k_nope, (c_1 @ block["w_uk"]).reshape(1, heads, -1), at, 0),
            put(r, cr_1[:, latent:], at, 0),
            put(v, (c_1 @ block["w_uv"]).reshape(1, heads, -1), at, 0),
            handed[None])[0])
    return a + f.reshape(seq - start, -1), cr, k_i, found


def held_part_off(cfg, block, probe):
    """How far the held experts' part a step served lies from the
    reference's: `probe` is {"in": the routed layer's input [rows, 1,
    hidden], "idx": the experts the step's router chose [rows, top_k],
    "out": what its held experts gave for them [rows, 1, hidden]} as the
    step computed them; the reference's routed sum of the same input
    under the same choice (its own float32 scores of it, its own
    weights) is what "out" is held to, as the root mean square of the
    difference over the reference's.  Where no row chose a held expert
    both parts are zero and the distance is 0.  `block`: the layer's
    parameters in float32.  No choice of experts is judged here."""
    u, idx, out = (jnp.asarray(probe[k]) for k in ("in", "idx", "out"))
    u = u.reshape(-1, u.shape[-1]).astype(jnp.float32)

    @jax.jit
    def want_of(block, u, idx):
        with jax.default_matmul_precision("highest"):
            return routed(cfg, block, u, cfg.get("first_expert", 0), idx)[0]

    want = want_of(block, u, idx)
    diff = out.reshape(want.shape).astype(jnp.float32) - want
    off, size = (float(jnp.mean(jnp.square(a))) for a in (diff, want))
    if size == 0.0:
        # no row of the step chose a held expert (of 16 rows in one layer
        # in 600: two rows in three choose none): the part is zero on
        # both sides, or the step put something where nothing belongs
        return 0.0 if off == 0.0 else float("inf")
    return (off / size) ** 0.5


def _f32(tree):
    return jax.tree_util.tree_map(lambda a: jnp.asarray(a, jnp.float32),
                                  tree)


def _layers(cfg, embedded, block_of, each):
    """The sequences whose embeddings are `embedded` ([n] x [seq,
    hidden]) through every layer, one layer's parameters held at a time:
    `each(i, block, row, x)` is called for every layer and sequence with
    the layer's input and gives its output; the last layer's come back."""
    xs = list(embedded)
    for i in range(cfg["num_hidden_layers"]):
        block = _f32(block_of(i))
        for row, x in enumerate(xs):
            out = each(i, block, row, x)
            xs[row] = out
        del block
    return xs


def session(cfg, ends, block_of, documents, query_block, keep=()):
    """([layers] x (latents [documents, seq, latent + rope], index keys
    [documents, seq, dim]), {document: [layers] x [seq, hidden]}) in
    float32, on the host: the caches a prefill pool would hand over for
    the seeded `documents` [documents, seq], for their caller to round
    once to the caches' types; and, for the documents `keep` names, the
    input every layer had, from which `gaps` continues a sequence that
    starts with that document (`prefix`).  The last layer's caches need
    its input alone: its attention and feed-forward are not run."""
    import numpy as np

    first = cfg.get("first_expert", 0)
    layers = cfg["num_hidden_layers"]
    ends = _f32(ends)

    @jax.jit
    def one(block, x):
        with jax.default_matmul_precision("highest"):
            y, cr, k_i, _ = layer(cfg, block, x, first, query_block)
        return y, cr, k_i

    @jax.jit
    def caches_alone(block, x):
        with jax.default_matmul_precision("highest"):
            h = rms_norm(x, block["input_norm"], cfg["rms_norm_eps"])
            _, cr, k_i = cached(cfg, block, h, jnp.arange(x.shape[0]))
        return x, cr, k_i

    kept = [([], []) for _ in range(layers)]
    inputs = {int(d): [] for d in keep}

    def each(i, block, row, x):
        if row in inputs:
            inputs[row].append(np.asarray(jax.device_get(x)))
        y, cr, k_i = (one if i < layers - 1 else caches_alone)(block, x)
        kept[i][0].append(jax.device_get(cr))
        kept[i][1].append(jax.device_get(k_i))
        return y

    _layers(cfg, ends["embed"][jnp.asarray(documents)], block_of, each)
    return [(np.stack(cr), np.stack(k_i)) for cr, k_i in kept], inputs


def gaps(cfg, ends, block_of, tokens, start, served, query_block,
         last=None, with_block=None, prefix=None):
    """(`[sequences, served length]` float32: at every served position,
    how far the reference's logit of the served token lies below the
    reference's best; and per layer what `layer` finds for `last`).

    `tokens` [sequences, seq] are document, question and served tokens of
    each checked row (seq a multiple of `query_block`); served token i
    was chosen from the logits at position start + i.  `ends` is
    {"embed", "norm_f", "head"}; `block_of(i)` gives block i's
    parameters, asked for once a layer and dropped before the next.
    `last` = {"at", "live", "attn_in": [layers] x [sequences, hidden],
    "selected": [layers] x [sequences, top_k]} describes the call's last
    step for the same rows (`layer`'s `last`); the second result
    is then {"shared": [layers] x [sequences], "attn": [layers] x
    [sequences, hidden]}.  `with_block(i, block)` is called with block i
    in float32 while it is held.  `prefix` = [sequences] x [layers] x [n,
    hidden]: every layer's input over a sequence's first n positions as
    `session` kept it (n <= start, the same for all); the forward then
    runs for the positions after them alone (`layer`'s `start`), which
    is the whole forward's result there at a sixteenth of its work."""
    first = cfg.get("first_expert", 0)
    count = served.shape[1]
    ends = _f32(ends)
    found = {"shared": [[] for _ in range(cfg["num_hidden_layers"])],
             "attn": [[] for _ in range(cfg["num_hidden_layers"])]}

    known = 0 if prefix is None else prefix[0][0].shape[0]

    @jax.jit
    def one(block, x, last):
        with jax.default_matmul_precision("highest"):
            y, _, _, found = layer(cfg, block, x, first, query_block, last,
                                   known)
        return y, found

    def each(i, block, row, x):
        handed = None if last is None else (
            jnp.asarray(last["at"], jnp.int32),
            jnp.asarray(last["attn_in"][i][row], jnp.float32),
            jnp.asarray(last["selected"][i][row], jnp.int32),
            jnp.asarray(last["live"], jnp.int32))
        if known:
            x = jnp.concatenate([jnp.asarray(prefix[row][i]), x])
        y, got = one(block, x, handed)
        if got is not None:
            found["shared"][i].append(float(got[0]))
            found["attn"][i].append(jax.device_get(got[1]))
        if with_block is not None and row == 0:
            with_block(i, block)
        return y

    xs = _layers(cfg, ends["embed"][jnp.asarray(tokens)[:, known:]],
                 block_of, each)
    start -= known

    @jax.jit
    def head_gaps(ends, x, served):
        with jax.default_matmul_precision("highest"):
            z = rms_norm(x[start:start + count], ends["norm_f"],
                         cfg["rms_norm_eps"]) @ ends["head"]
        picked = jnp.take_along_axis(z, served[:, None], axis=-1)
        return jnp.max(z, axis=-1) - picked[:, 0]

    return jnp.stack([head_gaps(ends, x, jnp.asarray(row))
                      for x, row in zip(xs, served)]), found
