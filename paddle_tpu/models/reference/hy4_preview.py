"""Plain float32 reference of one chip's share of Hy4-preview
(huggingface.co/tencent/Hy4-preview `config.json`, `model_type` `hy_v4`):
the full-sequence forward pass in straightforward `jax.numpy`, nothing
absorbed, no cache, no gather and no kernel, the highest matmul precision
where a caller sets it, nothing imported from the program.

The model.  A token's residual is X [n, d], n = `hc_mult` streams of the
hidden width, X_0 the embedding repeated n times.  Around **each**
sub-layer F (the attention, the feed-forward), with parameters of the
sub-layer's own (manifold-constrained hyper-connections,
arXiv:2512.24880):

    x~    = RMSNorm(vec(X); eps = hc_eps)                  [n d], no scale
    Hpre  = sigmoid(a_pre  (x~ P_pre)  + b_pre)            [n]
    Hpost = hc_magnitude sigmoid(a_post (x~ P_post) + b_post)   [n]
    Hres  = SK(exp(a_res mat(x~ P_res) + b_res))           [n, n]
    u     = sum_j Hpre[j] X_j ;  y = F(RMSNorm_w(u))
    X'_i  = sum_j Hres[i, j] X_j + Hpost[i] y

SK: `hc_sinkhorn_iterations` times (rows over their sums, then columns
over theirs).  The three projections lie side by side in one matrix
`hc_<sub>_p` [n d, n n + 2 n] (`pre | post | res`, the res columns
row-major in [i, j]), the three scalars in `hc_<sub>_a` [3] and the
biases in `hc_<sub>_b`, for <sub> = attn, mlp.  After the last layer
sum_i X_i, RMSNorm, the head.

Attention, for h the sub-layer's normed input, per head j of
`num_attention_heads` (DeepSeek-V3.2's latent attention without YaRN):

    c_q = N_q(h W_dq) ;  q_j = [c_q W_uq_nope,j | rope(c_q W_uq_rope,j)]
    [c | k_r] = h W_dkv ;  c = N_kv(c) ;  k_r = rope(k_r)
    k_j,s = [c_s W_uk,j | k_r,s] ;  v_j,s = c_s W_uv,j

On a layer `indexer_types` calls "full" the indexer chooses, with m over
`index_n_heads` heads:

    qI_m = rope_64(c_q W_iq,m) ;  kI_s = rope_64(LayerNorm(h_s W_ik))
    w = h W_iw * index_n_heads^-0.5 * index_head_dim^-0.5
    I_s = sum_m w_m relu(qI_m . kI_s) ;  S_t = the index_topk largest I_s, s <= t

and on a "shared" layer S_t is that of the nearest "full" layer below.
Over S_t and a sink z_j (a parameter a head, no value):

    p_j,s = exp(a_j,s) / (exp(z_j) + sum_{s' in S_t} exp(a_j,s'))
    a_j,s = q_j . k_j,s * (nope + rope)^-0.5 ;  o_j = sum_s p_j,s v_j,s
    out = (concat_j o_j * sigmoid(h W_g)) W_o

Feed-forward: down(silu(min(gate, L)) * clip(up, -L, L)), L =
`swiglu_limit`, in the dense layers, the shared expert and every routed
expert.  The router in float32: s = sigmoid(h W_r), the
`num_experts_per_tok` largest of s + b, weights s / sum(s over them) *
`routed_scaling_factor`; the held experts' part (`first`, and as many as
`w_gate` holds) plus the shared expert; what absent experts would add is
left out, as in the program.

Two uses.  `forward`: logits at every position of a few short sequences,
what the CPU tests hold `fluid.ProgramDecoder` to.  And for the
benchmark's cell, over sequences of 32k positions a layer and a sequence
at a time, queries in blocks and heads in groups so that it fits:
`session`, the caches a decode-pool chip is handed (every layer's `c |
k_r` and, on the layers that choose, `kI`); `gaps`, by how much the
reference's logit of each served token lies below the reference's best,
continued from the session's own float32 caches (a position's output
reads nothing after it, and of the positions before it a layer reads
their cache entries alone); and beside the gaps, for the call's last
step, what each layer's chooser, attention and hyper-connection are held
to on the program's own input (`attention_part`'s `last`), and
`held_part_off` for its held experts.
"""

import math

import jax
import jax.numpy as jnp


def rms_norm(x, scale, eps):
    x = x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True)
                          + eps)
    return x if scale is None else x * scale


def layer_norm(x, scale, bias, eps):
    centred = x - jnp.mean(x, axis=-1, keepdims=True)
    return centred * jax.lax.rsqrt(
        jnp.mean(jnp.square(centred), axis=-1, keepdims=True) + eps) \
        * scale + bias


def inv_freq(cfg):
    dim = cfg["qk_rope_head_dim"]
    theta = float(cfg["rope_parameters"]["rope_theta"])
    return theta ** (-2.0 * jnp.arange(dim // 2, dtype=jnp.float32) / dim)


def rope(x, positions, freq):
    """x [seq, heads, dim] with the first 2 * len(freq) values of every
    head turned at `positions` [seq] (rotate-half), the rest as they
    are."""
    turned = 2 * freq.shape[0]
    angles = positions[:, None, None].astype(jnp.float32) * freq
    angles = jnp.concatenate([angles, angles], axis=-1)
    part = x[..., :turned]
    x1, x2 = part[..., :turned // 2], part[..., turned // 2:]
    rotated = jnp.concatenate([-x2, x1], axis=-1)
    return jnp.concatenate(
        [part * jnp.cos(angles) + rotated * jnp.sin(angles),
         x[..., turned:]], axis=-1)


# -- the residual's streams ----------------------------------------------------

def sinkhorn(m, iterations):
    for _ in range(iterations):
        m = m / jnp.sum(m, axis=-1, keepdims=True)
        m = m / jnp.sum(m, axis=-2, keepdims=True)
    return m


def hc_maps(cfg, block, sub, X):
    """(Hpre [seq, n], Hpost [seq, n], Hres [seq, n, n]) of the streams X
    [seq, n, d] for the sub-layer `sub` ("attn" or "mlp")."""
    seq, n, d = X.shape
    z = rms_norm(X.reshape(seq, n * d), None, cfg["hc_eps"]) \
        @ block["hc_%s_p" % sub]
    a, b = block["hc_%s_a" % sub], block["hc_%s_b" % sub]
    pre = jax.nn.sigmoid(a[0] * z[:, :n] + b[:n])
    post = cfg["hc_magnitude"] * jax.nn.sigmoid(
        a[1] * z[:, n:2 * n] + b[n:2 * n])
    res = jnp.exp(a[2] * z[:, 2 * n:] + b[2 * n:]).reshape(seq, n, n)
    return pre, post, sinkhorn(res, cfg["hc_sinkhorn_iterations"])


def hc_pre(X, pre):
    return jnp.einsum("sn,snd->sd", pre, X)


def hc_post(X, res, post, y):
    return jnp.einsum("sij,sjd->sid", res, X) + post[:, :, None] * y[:, None]


# -- attention -----------------------------------------------------------------

def cached(cfg, block, h, positions):
    """(c_q [seq, q_rank], [c | k_r] [seq, latent + rope], kI [seq, dim]
    or None on a layer without an indexer) of h [seq, hidden]: the normed
    query latent, and what the layer's caches hold."""
    eps, latent = cfg["rms_norm_eps"], cfg["kv_lora_rank"]
    freq = inv_freq(cfg)
    c_q = rms_norm(h @ block["w_dq"], block["q_norm"], eps)
    ckv = h @ block["w_dkv"]
    c = rms_norm(ckv[..., :latent], block["kv_norm"], eps)
    r = rope(ckv[..., latent:][:, None, :], positions, freq)[:, 0]
    k_i = None
    if "w_ik" in block:
        k_i = layer_norm(h @ block["w_ik"], block["ik_norm"],
                         block["ik_norm_b"], eps)
        k_i = rope(k_i[:, None, :], positions, freq)[:, 0]
    return c_q, jnp.concatenate([c, r], axis=-1), k_i


def index_scores(cfg, block, h, c_q, positions, k_i):
    """I [queries, keys] = sum_m w_m relu(qI_m . kI) for the queries h,
    c_q at `positions`, over all the keys `k_i` (no mask)."""
    heads, dim = cfg["index_n_heads"], cfg["index_head_dim"]
    q = rope((c_q @ block["w_iq"]).reshape(-1, heads, dim), positions,
             inv_freq(cfg))
    w = (h @ block["w_iw"]) * heads ** -0.5 * dim ** -0.5
    return jnp.einsum("qh,qhs->qs", w,
                      jax.nn.relu(jnp.einsum("qhd,sd->qhs", q, k_i)))


def select(scores, top_k, q_positions):
    """[queries, min(top_k, keys)] int32: of the keys s <= a query's
    position, those with the largest score, best first; only the first
    min(top_k, position + 1) of a row are slots (`mask_of`)."""
    keys = scores.shape[-1]
    causal = jnp.arange(keys)[None, :] <= q_positions[:, None]
    return jax.lax.top_k(jnp.where(causal, scores, -jnp.inf),
                         min(top_k, keys))[1].astype(jnp.int32)


def mask_of(selection, q_positions, keys):
    """The boolean mask [queries, keys] of the slots `selection` names:
    the first min(its width, position + 1) entries of each row."""
    count = jnp.minimum(selection.shape[1], q_positions + 1)
    slots = jnp.where(jnp.arange(selection.shape[1])[None, :]
                      < count[:, None], selection, keys)
    rows = jnp.arange(selection.shape[0])[:, None]
    return jnp.zeros((selection.shape[0], keys), bool).at[
        rows, slots].set(True, mode="drop")


def attend(cfg, group, c_q, positions, c, r, mask):
    """The heads of one group over the keys and values made from the
    latents `c` [keys, latent] and the rotated keys `r` [keys, rope],
    for the queries c_q at `positions` under `mask` [queries, keys], the
    group's sinks in the denominator: [queries, heads * value].  `group`
    holds the group's columns of w_uq_nope, w_uq_rope, w_uk, w_uv and its
    sinks."""
    heads = group["sink"].shape[0]
    nope, rope_dim = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"]
    k_nope = (c @ group["w_uk"]).reshape(-1, heads, nope)
    v = (c @ group["w_uv"]).reshape(c.shape[0], heads, -1)
    q_nope = (c_q @ group["w_uq_nope"]).reshape(-1, heads, nope)
    q_rope = rope((c_q @ group["w_uq_rope"]).reshape(-1, heads, rope_dim),
                  positions, inv_freq(cfg))
    scores = (jnp.einsum("qhd,khd->hqk", q_nope, k_nope)
              + jnp.einsum("qhd,kd->hqk", q_rope, r)) \
        * (nope + rope_dim) ** -0.5
    scores = jnp.where(mask[None], scores, -jnp.inf)
    sink = group["sink"][:, None, None]
    top = jnp.maximum(jnp.max(scores, axis=-1, keepdims=True), sink)
    e = jnp.exp(scores - top)
    p = e / (jnp.sum(e, axis=-1, keepdims=True) + jnp.exp(sink - top))
    out = jnp.einsum("hqk,khd->qhd", p, v)
    return out.reshape(out.shape[0], -1)


def _groups(cfg, block, count):
    """The heads' parameters, `count` groups of consecutive heads with a
    leading group axis."""
    heads = cfg["num_attention_heads"]
    each = heads // count

    def split(w, per_head):
        lead = w.shape[0]
        return jnp.moveaxis(w.reshape(lead, count, each * per_head), 1, 0)

    nope, rope_dim = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"]
    return {"w_uq_nope": split(block["w_uq_nope"], nope),
            "w_uq_rope": split(block["w_uq_rope"], rope_dim),
            "w_uk": split(block["w_uk"], nope),
            "w_uv": split(block["w_uv"], cfg["v_head_dim"]),
            "sink": block["sink"].reshape(count, each)}


def _blocks(x, size):
    """x [n, ...] as [n / size, size, ...]."""
    return x.reshape((x.shape[0] // size, size) + x.shape[1:])


def attention(cfg, block, h, c_q, positions, cr, selection, query_block,
              head_groups=1):
    """The attention sub-layer's output [queries, hidden] for the
    queries h, c_q at `positions` over the cache entries `cr` [keys,
    latent + rope] of every position, each query over the slots its row
    of `selection` names: a group of heads at a time, `query_block`
    queries at a time."""
    latent = cfg["kv_lora_rank"]
    c, r = cr[:, :latent], cr[:, latent:]
    keys = cr.shape[0]

    def a_group(group):
        def some_queries(part):
            c_q_b, at, chosen = part
            return attend(cfg, group, c_q_b, at, c, r,
                          mask_of(chosen, at, keys))

        o = jax.lax.map(some_queries, tuple(
            _blocks(a, query_block) for a in (c_q, positions, selection)))
        return o.reshape((-1,) + o.shape[2:])

    o = jax.lax.map(a_group, _groups(cfg, block, head_groups))
    o = jnp.moveaxis(o, 0, 1).reshape(h.shape[0], -1)
    return (o * jax.nn.sigmoid(h @ block["w_g"])) @ block["wo"]


def attention_part(cfg, block, X, query_block, prefix=None, selection=None,
                   last=None, head_groups=1):
    """(X' [new, n, d], [c | k_r] [new, latent + rope], kI [new, dim] or
    None, the selection [new, top_k] the layer attended, what `last` asks
    for) of one layer's attention sub-layer with its hyper-connection,
    over one sequence's positions from `start` on: X [new, n, d] are
    their streams, `prefix` = ([c | k_r] [start, .], kI [start, .] or
    None) the layer's cache entries of the positions before them (None:
    start = 0), `new` a multiple of `query_block`.

    A layer that chooses (its block holds `w_ik`) makes its own
    selection; any other attends `selection` [new, top_k], what the
    nearest choosing layer below it attended.

    `last` = (at, h [d], selected [top_k] int32, live, X_1 [n, d], y_1
    [d]): the position of a decode step, the normed input the program's
    attention sub-layer had there, the slots it attended and how many of
    them are live, the streams it read the input off and the output it
    wrote back.  The fifth result is then what the reference makes of
    those at that position (its own float32 weights and arithmetic, its
    own cache entries of the positions before, the slot `at` itself made
    from `h`): `shared`, how many of the slots it would choose are among
    the program's, as a share (None on a layer that does not choose);
    `attn`, the sub-layer's output [d] over the program's set; `streams`,
    the streams [n, d] its hyper-connection writes for X_1 and y_1.  No
    upstream layer's drift is in any: each judges what this layer did
    with what it was given."""
    eps, top_k = cfg["rms_norm_eps"], cfg["index_topk"]
    start = 0 if prefix is None else prefix[0].shape[0]
    positions = start + jnp.arange(X.shape[0])
    pre, post, res = hc_maps(cfg, block, "attn", X)
    h = rms_norm(hc_pre(X, pre), block["input_norm"], eps)
    c_q, cr_new, k_i_new = cached(cfg, block, h, positions)
    cr, k_i = cr_new, k_i_new
    if prefix is not None:
        cr = jnp.concatenate([prefix[0], cr_new])
        if k_i_new is not None:
            k_i = jnp.concatenate([prefix[1], k_i_new])
    if k_i is not None:
        def choose(part):
            h_b, c_q_b, at = part
            return select(index_scores(cfg, block, h_b, c_q_b, at, k_i),
                          top_k, at)

        selection = jax.lax.map(choose, tuple(
            _blocks(a, query_block) for a in (h, c_q, positions)))
        selection = selection.reshape((-1,) + selection.shape[2:])
    y = attention(cfg, block, h, c_q, positions, cr, selection, query_block,
                  head_groups)
    found = None
    if last is not None:
        at, h_1, selected, live, X_1, y_1 = last
        one, h_1 = jnp.reshape(at, (1,)), h_1[None]
        c_q_1, cr_1, k_i_1 = cached(cfg, block, h_1, one)
        put = jax.lax.dynamic_update_slice_in_dim
        shared = None
        if k_i is not None:
            own = mask_of(select(index_scores(
                cfg, block, h_1, c_q_1, one, put(k_i, k_i_1, at, 0)),
                top_k, one), one, cr.shape[0])[0]
            handed = jnp.zeros((cr.shape[0],), bool).at[
                jnp.where(jnp.arange(selected.shape[0]) < live, selected,
                          cr.shape[0])].set(True, mode="drop")
            shared = jnp.sum(own & handed) / jnp.sum(own)
        # the program's set as a selection row: its live entries first
        # (`mla_index_select` hands the live slots over first)
        chosen = jnp.where(jnp.arange(selected.shape[0]) < live, selected,
                           cr.shape[0])[None]
        # `mask_of` counts min(width, position + 1) = live entries
        attn = attention(cfg, block, h_1, c_q_1, one, put(cr, cr_1, at, 0),
                         chosen, 1, head_groups)[0]
        maps = hc_maps(cfg, block, "attn", X_1[None])
        found = (shared, attn,
                 hc_post(X_1[None], maps[2], maps[1], y_1[None])[0])
    return hc_post(X, res, post, y), cr_new, k_i_new, selection, found


# -- feed-forward --------------------------------------------------------------

def gated(cfg, u, w_in, w_out):
    gate, up = jnp.split(u @ w_in, 2, axis=-1)
    return _clamped(cfg, gate, up) @ w_out


def _clamped(cfg, gate, up):
    limit = cfg.get("swiglu_limit")
    if limit:
        gate, up = jnp.minimum(gate, limit), jnp.clip(up, -limit, limit)
    return jax.nn.silu(gate) * up


def route(cfg, block, u, indices=None):
    """(weights [tokens, scored], indices [tokens, top_k]) of u [tokens,
    hidden]: a token's weight of each scored expert, 0 where it is not
    among its chosen (the reference's own choice, by s + b over all the
    experts scored, or `indices` where a caller hands it a routing); the
    weights read s."""
    scores = jax.nn.sigmoid(u @ block["router"])
    if indices is None:
        indices = jax.lax.top_k(scores + block["router_bias"],
                                cfg["num_experts_per_tok"])[1]
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
        hidden = _clamped(cfg, u @ w_gate, u @ w_up)
        return m + weight[:, None] * (hidden @ w_down), None

    m, _ = jax.lax.scan(add_expert, jnp.zeros_like(u), (
        block["w_gate"], block["w_up"], block["w_down"], held.T))
    return m, indices


def feed_forward(cfg, block, u, first=0, shared=True):
    """F(u) for u [tokens, hidden]; `shared` False leaves the shared
    expert out (the shares of a layer count it once)."""
    if "ffn_in" in block:
        return gated(cfg, u, block["ffn_in"], block["ffn_out"])
    m = routed(cfg, block, u, first)[0]
    return m + gated(cfg, u, block["shared_in"], block["shared_out"]) \
        if shared else m


def ffn_part(cfg, block, X, first=0):
    """X' [seq, n, d] of one layer's feed-forward sub-layer with its
    hyper-connection over the streams X [seq, n, d], 2048 tokens of the
    feed-forward at a time."""
    pre, post, res = hc_maps(cfg, block, "mlp", X)
    u = rms_norm(hc_pre(X, pre), block["pre_mlp_norm"], cfg["rms_norm_eps"])
    tokens = math.gcd(u.shape[0], 2048)
    f = jax.lax.map(lambda part: feed_forward(cfg, block, part, first),
                    _blocks(u, tokens))
    return hc_post(X, res, post, f.reshape(u.shape))


def head(cfg, ends, X):
    """Logits [seq, vocab] of the streams X [seq, n, d] after the last
    layer."""
    return rms_norm(jnp.sum(X, axis=1), ends["norm_f"],
                    cfg["rms_norm_eps"]) @ ends["head"]


def streams_of(cfg, embedded):
    """X_0 [seq, n, d]: the embedding [seq, d] repeated."""
    return jnp.broadcast_to(embedded[:, None, :],
                            (embedded.shape[0], cfg["hc_mult"],
                             embedded.shape[1]))


def forward(cfg, params, tokens, held=None):
    """Logits [batch, seq, vocab] of `tokens` [batch, seq]: every
    sequence through every layer, whole.  `params` is {"embed", "blocks":
    [a dict a layer], "norm_f", "head"}; `held` = (first, count) names
    the experts the blocks hold."""
    first = held[0] if held else 0
    seq = tokens.shape[1]

    def one(row):
        X = streams_of(cfg, params["embed"][row])
        selection = None
        for block in params["blocks"]:
            X, _, _, selection, _ = attention_part(
                cfg, block, X, seq, selection=selection)
            X = ffn_part(cfg, block, X, first)
        return head(cfg, params, X)

    with jax.default_matmul_precision("highest"):
        return jnp.stack([one(row) for row in tokens])


# -- the benchmark's cell ------------------------------------------------------

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
        return 0.0 if off == 0.0 else float("inf")
    return (off / size) ** 0.5


_FFN = ("pre_mlp_norm", "hc_mlp_p", "hc_mlp_a", "hc_mlp_b", "ffn_in",
        "ffn_out", "shared_in", "shared_out", "router", "router_bias",
        "w_gate", "w_up", "w_down")


def _f32(tree):
    return jax.tree_util.tree_map(lambda a: jnp.asarray(a, jnp.float32),
                                  tree)


def _halves(block):
    """(the attention sub-layer's parameters, the feed-forward's) of a
    block, in float32, each made when it is asked for: a layer's experts
    are 2.4 GB in float32 beside 3 GB of streams a sequence."""
    return (lambda: _f32({k: v for k, v in block.items() if k not in _FFN}),
            lambda: _f32({k: v for k, v in block.items() if k in _FFN}))


def session(cfg, ends, block_of, documents, query_block, head_groups=1):
    """[layers] x (latents [documents, seq, latent + rope], index keys
    [documents, seq, dim] or None) in float32, on the host: the caches a
    prefill pool would hand over for the seeded `documents` [documents,
    seq], for their caller to round once to the caches' types, and for
    `gaps` to continue a sequence from.  The last layer's caches need its
    attention's input alone: its attention and feed-forward are not
    run."""
    import numpy as np

    first = cfg.get("first_expert", 0)
    layers = cfg["num_hidden_layers"]
    embed = jnp.asarray(ends["embed"], jnp.float32)

    @jax.jit
    def attend_all(block, X, selection):
        with jax.default_matmul_precision("highest"):
            out, cr, k_i, selection, _ = attention_part(
                cfg, block, X, query_block, selection=selection,
                head_groups=head_groups)
        return out, cr, k_i, selection

    @jax.jit
    def caches_alone(block, X):
        with jax.default_matmul_precision("highest"):
            pre = hc_maps(cfg, block, "attn", X)[0]
            h = rms_norm(hc_pre(X, pre), block["input_norm"],
                         cfg["rms_norm_eps"])
            _, cr, k_i = cached(cfg, block, h, jnp.arange(X.shape[0]))
        return cr, k_i

    @jax.jit
    def feed(block, X):
        with jax.default_matmul_precision("highest"):
            return ffn_part(cfg, block, X, first)

    kept = [([], []) for _ in range(layers)]
    for row in documents:
        X = streams_of(cfg, embed[jnp.asarray(row)])
        selection = None
        for i in range(layers):
            attn_of, ffn_of = _halves(block_of(i))
            if i == layers - 1:
                cr, k_i = caches_alone(attn_of(), X)
            else:
                X, cr, k_i, selection = attend_all(attn_of(), X, selection)
                X = feed(ffn_of(), X)
            kept[i][0].append(np.asarray(jax.device_get(cr)))
            kept[i][1].append(None if k_i is None
                              else np.asarray(jax.device_get(k_i)))
        del X, selection
    return [(np.stack(cr), None if k_i[0] is None else np.stack(k_i))
            for cr, k_i in kept]


def gaps(cfg, ends, block_of, tokens, prefixes, served, query_block,
         last=None, with_block=None, head_groups=1):
    """(`[sequences, served length]` float32: at every served position,
    how far the reference's logit of the served token lies below the
    reference's best; and per layer what `attention_part` finds for
    `last`).

    `tokens` [sequences, new] are the question and the served tokens of
    each checked row (`new` a multiple of `query_block`), `prefixes`
    [sequences] x [layers] x ([c | k_r] [start, .], kI [start, .] or
    None) the float32 cache entries of the document before them as
    `session` made them; served token i was chosen from the logits at
    position start + new - served length - 1 + i.  `ends` is {"embed",
    "norm_f", "head"}; `block_of(i)` gives block i's parameters, asked
    for once a layer.  `last` = {"at", "live", "attn_in": [layers] x
    [sequences, hidden], "selected": [layers] x [sequences, top_k] (a
    layer that does not choose: the set it was handed), "streams_in":
    [layers] x [sequences, n, hidden], "attn_out": [layers] x [sequences,
    hidden]} describes the call's last step for the same rows; the
    second result is then {"shared": [layers] x [sequences] (None where a
    layer does not choose), "attn": [layers] x [sequences, hidden],
    "streams": [layers] x [sequences, n, hidden]}.  `with_block(i,
    block)` is called with block i's feed-forward half in float32 while
    it is held."""
    first = cfg.get("first_expert", 0)
    layers = cfg["num_hidden_layers"]
    count = served.shape[1]
    ends = _f32(ends)
    found = {key: [[] for _ in range(layers)]
             for key in ("shared", "attn", "streams")}

    @jax.jit
    def attend_new(block, X, prefix, selection, last):
        with jax.default_matmul_precision("highest"):
            out, _, _, selection, got = attention_part(
                cfg, block, X, query_block, prefix, selection, last,
                head_groups)
        return out, selection, got

    @jax.jit
    def feed(block, X):
        with jax.default_matmul_precision("highest"):
            return ffn_part(cfg, block, X, first)

    tokens = jnp.asarray(tokens)
    xs = [streams_of(cfg, ends["embed"][row]) for row in tokens]
    selections = [None] * len(xs)
    for i in range(layers):
        attn_of, ffn_of = _halves(block_of(i))
        block = attn_of()
        for row, X in enumerate(xs):
            handed = None if last is None else (
                jnp.asarray(last["at"], jnp.int32),
                jnp.asarray(last["attn_in"][i][row], jnp.float32),
                jnp.asarray(last["selected"][i][row], jnp.int32),
                jnp.asarray(last["live"], jnp.int32),
                jnp.asarray(last["streams_in"][i][row], jnp.float32),
                jnp.asarray(last["attn_out"][i][row], jnp.float32))
            prefix = tuple(None if a is None else jnp.asarray(a)
                           for a in prefixes[row][i])
            xs[row], selections[row], got = attend_new(
                block, X, prefix, selections[row], handed)
            if got is not None:
                found["shared"][i].append(
                    None if got[0] is None else float(got[0]))
                found["attn"][i].append(jax.device_get(got[1]))
                found["streams"][i].append(jax.device_get(got[2]))
        del block
        block = ffn_of()
        xs = [feed(block, X) for X in xs]
        if with_block is not None:
            with_block(i, block)
        del block

    @jax.jit
    def head_gaps(ends, X, served):
        with jax.default_matmul_precision("highest"):
            z = head(cfg, ends, X[-count - 1:-1])
        picked = jnp.take_along_axis(z, served[:, None], axis=-1)
        return jnp.max(z, axis=-1) - picked[:, 0]

    return jnp.stack([head_gaps(ends, X, jnp.asarray(row))
                      for X, row in zip(xs, served)]), found
