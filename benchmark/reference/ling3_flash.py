"""Plain float32 reference of one chip's share of Ling-3.0-flash
(huggingface.co/inclusionAI/Ling-3.0-flash, `model_type`
`bailing_hybrid`): the full-sequence forward pass in straightforward
`jax.numpy`, the delta rule under a gate a key channel (Kimi Delta
Attention, arXiv:2510.26692) **position by position** (`lax.scan` over
the sequence, a [128] decay a head: no chunk, no state handed in, no
kernel), latent attention **unabsorbed** (every head's keys and values
made from the latents for the whole sequence, the whole score matrix, no
cache), every held expert applied to every token and weighted by the
routing weights, highest matmul precision, nothing imported from the
program.

The model.  x [batch, seq, hidden]; N(x; w) = x / sqrt(mean(x^2) + eps)
* w; no bias anywhere; layer l is latent attention where (l + 1) %
`layer_group_size` == 0, else KDA; the first `first_k_dense_replace`
layers' feed-forward is dense:

    a = x + mixer(N(x; w1))
    y = a + ffn(N(a; w2))

KDA layer, u = N(x; w1), H = `num_attention_heads` heads of `head_dim`
D on both sides of the state:

    [q | k | v | f] = u W_qkvf;  [b | z] = u W_bz
    [q | k | v] = silu(conv([q | k | v]; F))    depthwise, causal, width
                                                `short_conv_kernel_size`
    q_h = q_h / sqrt(sum q_h^2 + 1e-6) / sqrt(D);  k_h likewise, unscaled
    beta = sigmoid(b)                                        [H]
    g = `kda_lower_bound` * sigmoid(exp(A_log[h]) (f + dt_bias))
                                                [H, D], in [-5, 0)
    per head, S [D, D] from zeros, position by position:
        S = diag(exp(g_t)) S;  r = S^T k_t;
        S = S + k_t (beta_t (v_t - r))^T;  o_t = S^T q_t
    y_h = N(o_h; w_n) * sigmoid(z_h);   mixer = concat_h(y_h) W_o

Latent layer (MLA with a full-rank query), H heads of
`qk_nope_head_dim` + `qk_rope_head_dim` query/key values and
`v_head_dim` values over a latent of `kv_lora_rank`:

    q_nope = u W_q,nope;  q_rope = rope(u W_q,rope)          per head
    [c | r] = u W_dkv;  c = N(c; w_kv);  r = rope(r)         one shared
    k_h = [c W_uk,h | r];  v_h = c W_uv,h
    o_h = softmax_causal([q_nope,h | q_rope,h] k_h^T / sqrt(192)) v_h
    mixer = concat_h(o_h * sigmoid((u W_z)_h)) W_o

(rope: rotate-half over the 64 rotated values, `rope_theta`.)

Expert layer, s = N(a; w2):

    p = sigmoid(s W_r) over the `scored_experts`
    the choice: by p + bias inside the best `topk_group` of `n_group`
    groups of consecutive experts (a group scores the sum of its two
    largest), the `num_experts_per_tok` largest;
    w_j = `routed_scaling_factor` p_j / sum_j p_j
    ffn = E_shared(s) + sum_j w_j E_{e_j}(s)

of which a share holds the experts first .. first + count - 1
(`first_expert`, and `w_gate.shape[0]` of them): the sum then runs over
the j whose e_j lies there (the shared expert is replicated, whole in
every share: `shared=False` leaves it out, for adding shares up).  After
the last layer z = N(x; w_f) W_head over the rows of the vocabulary the
share holds; token ids are local to them.

`params`: {"embed" [vocab, hidden], "blocks": [{"input_norm",
"pre_mlp_norm"; a dense layer "ffn_in" [hidden, 2 * width] (gate columns
first), "ffn_out"; an expert layer "shared_in", "shared_out", "router"
[hidden, scored], "router_bias" [scored], "w_gate", "w_up" [count,
hidden, width], "w_down" [count, width, hidden]; a KDA layer "w_qkvf",
"w_bz", "conv" [channels, width], "a_log" [H], "dt_bias" [H * D],
"out_norm" [D], "wo"; a latent layer "wq_nope", "wq_rope", "w_dkv",
"kv_norm", "w_uk", "w_uv", "w_z", "wo"}], "norm_f", "head" [hidden,
vocab]}, matrices as [in, out].  `cfg` has the source's keys, and
`scored_experts` and `first_expert` of a share.

`cfg["control"]`, where present, makes the reference **wrong** in one
named way (a check that `correct`'s limits refuse a program that
computes something else: benchmark/tests/hybrid_control.py,
scripts/ling3_check.py): "gate": "head" takes the mean of a head's 128
gates for all of them (what the rule under a gate a head computes: the
control that tells KDA from Gated DeltaNet); "floor": False drops the
lower bound (g = -exp(A_log) softplus(f + dt_bias)); {"state":
"bfloat16"} rounds the state after every position; "beta": 1 takes beta
as 1; "read": False leaves `S^T k` out; "tail_cut": p starts the
convolution from zeros again at position p; "out_gate": False leaves
both mixers' head-wise gates out; "latent_norm": False leaves the
latent's norm out; "rotary": 192 rotates all 192 values of a query and a
key head; "drop": True drops every token's last chosen expert.
"""

import math

import jax
import jax.numpy as jnp

KDA, LATENT = "linear_attention", "latent_attention"


def layer_type(cfg, index):
    return LATENT if (index + 1) % cfg["layer_group_size"] == 0 else KDA


def _control(cfg, key, default):
    return (cfg.get("control") or {}).get(key, default)


def rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(
        jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps) * scale


def rope(x, positions, theta):
    """x [batch, seq, heads, dim] turned at `positions` [seq]: x cos +
    rotate_half(x) sin, the two halves of a head paired."""
    dim = x.shape[-1]
    inv_freq = 1.0 / theta ** (jnp.arange(0, dim, 2, dtype=jnp.float32)
                               / dim)
    angles = positions[:, None, None].astype(jnp.float32) * inv_freq
    angles = jnp.concatenate([angles, angles], axis=-1)
    x1, x2 = x[..., :dim // 2], x[..., dim // 2:]
    rotated = jnp.concatenate([-x2, x1], axis=-1)
    return x * jnp.cos(angles) + rotated * jnp.sin(angles)


def gated(u, w_in, w_out):
    gate, up = jnp.split(u @ w_in, 2, axis=-1)
    return (jax.nn.silu(gate) * up) @ w_out


def causal_conv(x, filt, cut=None):
    """out_t = sum_j filt[:, j] x_{t - (K - 1) + j}, zeros before
    position 0 (and, with `cut`, before position `cut` again for the
    positions from it on: a tail that is not carried)."""
    width, seq = filt.shape[1], x.shape[1]
    padded = jnp.pad(x, ((0, 0), (width - 1, 0), (0, 0)))
    at = jnp.arange(seq)[:, None]
    out = 0.0
    for j in range(width):
        taken = padded[:, j:j + seq]
        if cut is not None:
            source = at - (width - 1) + j
            taken = jnp.where((at >= cut) & (source < cut), 0.0, taken)
        out = out + taken * filt[:, j]
    return out


def delta_rule(cfg, q, k, v, g, beta):
    """(o [batch, seq, H, D], the state after the last position [batch,
    H, D, D]) of the delta rule under a gate a key channel, position by
    position from a zero state: q, k (normed), v [batch, seq, H, D], g
    [batch, seq, H, D] (row d of a head's state decays by exp(g[d])),
    beta [batch, seq, H]."""
    kept = _control(cfg, "state", None)

    def step(s, at):
        q_t, k_t, v_t, g_t, b_t = at
        s = s * jnp.exp(g_t)[..., :, None]
        held = jnp.einsum("bhkv,bhk->bhv", s, k_t) \
            if _control(cfg, "read", True) else 0.0
        s = s + k_t[..., :, None] * (b_t[..., None]
                                     * (v_t - held))[..., None, :]
        out = jnp.einsum("bhkv,bhk->bhv", s, q_t)
        if kept == "bfloat16":
            # (an explicit rounding: XLA drops a cast down and up)
            s = jax.lax.reduce_precision(s, exponent_bits=8,
                                         mantissa_bits=7)
        return s, out

    batch, _, heads, dim = q.shape
    state = jnp.zeros((batch, heads, dim, v.shape[-1]), jnp.float32)
    state, out = jax.lax.scan(
        step, state, tuple(jnp.moveaxis(t, 1, 0)
                           for t in (q, k, v, g, beta)))
    return jnp.moveaxis(out, 0, 1), state


def kda_gate(cfg, block, f):
    """g [batch, seq, H, D] of the gate's projection f [batch, seq,
    H * D]."""
    heads, dim = cfg["num_attention_heads"], cfg["head_dim"]
    rated = jnp.exp(block["a_log"])[:, None] \
        * (f + block["dt_bias"]).reshape(f.shape[:2] + (heads, dim))
    if _control(cfg, "floor", True):
        g = cfg["kda_lower_bound"] * jax.nn.sigmoid(rated)
    else:
        g = -jnp.exp(block["a_log"])[:, None] * jax.nn.softplus(
            (f + block["dt_bias"]).reshape(rated.shape))
    if _control(cfg, "gate", "channel") == "head":
        g = jnp.broadcast_to(jnp.mean(g, axis=-1, keepdims=True), g.shape)
    return g


def kda_mixer(cfg, block, h):
    """(the KDA mixer of h [batch, seq, hidden], the state after the
    last position)."""
    batch, seq, _ = h.shape
    heads, dim = cfg["num_attention_heads"], cfg["head_dim"]
    width = heads * dim
    mixed = h @ block["w_qkvf"]
    qkv, f = mixed[..., :3 * width], mixed[..., 3 * width:]
    b, z = jnp.split(h @ block["w_bz"], 2, axis=-1)
    qkv = jax.nn.silu(causal_conv(qkv, block["conv"],
                                  _control(cfg, "tail_cut", None)))
    q, k, v = (t.reshape(batch, seq, heads, dim)
               for t in jnp.split(qkv, 3, axis=-1))

    def l2norm(t):
        return t * jax.lax.rsqrt(
            jnp.sum(jnp.square(t), axis=-1, keepdims=True) + 1e-6)

    beta = jax.nn.sigmoid(b)
    if _control(cfg, "beta", None) is not None:
        beta = jnp.full_like(beta, _control(cfg, "beta", None))
    o, state = delta_rule(cfg, l2norm(q) / math.sqrt(dim), l2norm(k), v,
                          kda_gate(cfg, block, f), beta)
    y = rms_norm(o, block["out_norm"], cfg["rms_norm_eps"])
    if _control(cfg, "out_gate", True):
        y = y * jax.nn.sigmoid(z)[..., None]
    return y.reshape(batch, seq, -1) @ block["wo"], state


def latent_mixer(cfg, block, h):
    """Latent attention of h [batch, seq, hidden], every head's keys and
    values made from the latents, its output gated a head."""
    batch, seq, _ = h.shape
    heads, eps = cfg["num_attention_heads"], cfg["rms_norm_eps"]
    theta, latent = cfg["rope_theta"], cfg["kv_lora_rank"]
    positions = jnp.arange(seq)
    q_nope = (h @ block["wq_nope"]).reshape(batch, seq, heads, -1)
    q_rope = (h @ block["wq_rope"]).reshape(batch, seq, heads, -1)
    ckv = h @ block["w_dkv"]
    c = ckv[..., :latent]
    if _control(cfg, "latent_norm", True):
        c = rms_norm(c, block["kv_norm"], eps)
    k_nope = (c @ block["w_uk"]).reshape(batch, seq, heads, -1)
    v = (c @ block["w_uv"]).reshape(batch, seq, heads, -1)
    r = jnp.broadcast_to(ckv[..., latent:][:, :, None, :],
                         k_nope.shape[:3] + (ckv.shape[-1] - latent,))
    if _control(cfg, "rotary", q_rope.shape[-1]) == q_rope.shape[-1]:
        q = jnp.concatenate([q_nope, rope(q_rope, positions, theta)], -1)
        k = jnp.concatenate([k_nope, rope(r, positions, theta)], -1)
    else:   # every value of a head turned
        q = rope(jnp.concatenate([q_nope, q_rope], -1), positions, theta)
        k = rope(jnp.concatenate([k_nope, r], -1), positions, theta)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) / math.sqrt(q.shape[-1])
    causal = jnp.tril(jnp.ones((seq, seq), bool))
    scores = jnp.where(causal, scores, -jnp.inf)
    out = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(scores, axis=-1), v)
    if _control(cfg, "out_gate", True):
        out = out * jax.nn.sigmoid(h @ block["w_z"])[..., None]
    return out.reshape(batch, seq, -1) @ block["wo"]


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
    if _control(cfg, "drop", False):
        top = top.at[:, -1].set(0.0)
    hot = indices[..., None] == jnp.arange(scores.shape[-1])
    return jnp.sum(jnp.where(hot, top[..., None], 0.0), axis=1), indices


def routed(cfg, block, u, first=0, indices=None):
    """(the held experts' part of the routed sum for u [tokens, hidden],
    the experts chosen): every held expert applied to every token, one
    after another (a scan), weighted by the token's weight of it."""
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


def feed_forward(cfg, block, u, first=0, indices=None, shared=True):
    """(F(u) for u [tokens, hidden], the experts chosen or None)."""
    if "ffn_in" in block:
        return gated(u, block["ffn_in"], block["ffn_out"]), None
    out, indices = routed(cfg, block, u, first, indices)
    if shared:
        out = out + gated(u, block["shared_in"], block["shared_out"])
    return out, indices


def layer(cfg, index, block, x, first=0, indices=None, shared=True):
    """(the layer's output, {"mixer": the mixer's output, "state": a KDA
    layer's state after the last position or None, "indices"}) for x
    [batch, seq, hidden]."""
    eps = cfg["rms_norm_eps"]
    h = rms_norm(x, block["input_norm"], eps)
    if layer_type(cfg, index) == KDA:
        mixer, state = kda_mixer(cfg, block, h)
    else:
        mixer, state = latent_mixer(cfg, block, h), None
    a = x + mixer
    u = rms_norm(a, block["pre_mlp_norm"], eps)
    f, indices = feed_forward(cfg, block, u.reshape(-1, u.shape[-1]), first,
                              indices, shared)
    return a + f.reshape(a.shape), {"mixer": mixer, "state": state,
                                    "indices": indices}


def _f32(tree):
    return jax.tree_util.tree_map(lambda a: jnp.asarray(a, jnp.float32),
                                  tree)


def forward(cfg, params, tokens):
    """{"logits" [batch, seq, vocab], "hidden": [L] each layer's output,
    "mixer": [L] each mixer's output, "states": [L] a KDA layer's state
    after the last position (None for a latent layer), "indices": [L]
    the experts chosen [tokens, top_k] (None for a dense layer)} for
    local token ids `tokens` [batch, seq]; `params` hold the experts
    from `cfg["first_expert"]` (default 0) on."""
    params = _f32(params)
    first = cfg.get("first_expert", 0)
    out = {"hidden": [], "mixer": [], "states": [], "indices": []}
    with jax.default_matmul_precision("highest"):
        x = params["embed"][tokens]
        for i, block in enumerate(params["blocks"]):
            x, found = layer(cfg, i, block, x, first)
            out["hidden"].append(x)
            out["mixer"].append(found["mixer"])
            out["states"].append(found["state"])
            out["indices"].append(found["indices"])
        out["logits"] = rms_norm(x, params["norm_f"],
                                 cfg["rms_norm_eps"]) @ params["head"]
    return out


def held_part_off(cfg, block, probe):
    """How far the held experts' part a step served lies from the
    reference's: `probe` is {"in": the routed layer's input [rows, 1,
    hidden], "idx": the experts the step's router chose [rows, top_k],
    "out": what its held experts gave for them [rows, 1, hidden]} as the
    step computed them; the reference's routed sum of the same input
    under the same choice (its own float32 scores of it) is what "out"
    is held to, as the root mean square of the difference over the
    reference's.  `block`: the layer's parameters in float32.  A choice
    of experts is not judged here (a near-tie falls either way between
    bfloat16 and float32): what the held experts' weights and products
    did to the rows they were given is."""
    u, idx, out = (jnp.asarray(probe[k]) for k in ("in", "idx", "out"))
    u = u.reshape(-1, u.shape[-1]).astype(jnp.float32)

    @jax.jit
    def want_of(block, u, idx):
        with jax.default_matmul_precision("highest"):
            return routed(cfg, block, u, cfg.get("first_expert", 0), idx)[0]

    want = want_of(block, u, idx)
    diff = out.reshape(want.shape).astype(jnp.float32) - want
    return float(jnp.sqrt(jnp.mean(jnp.square(diff))
                          / jnp.mean(jnp.square(want))))


def state_off(got, want):
    """The root mean square of a served state's difference from the
    reference's, over the reference's."""
    got, want = (jnp.asarray(t, jnp.float32) for t in (got, want))
    return float(jnp.sqrt(jnp.mean(jnp.square(got - want))
                          / jnp.mean(jnp.square(want))))


def gaps(cfg, ends, block_of, prompt, served, rows, with_block=None,
         with_state=None):
    """`[sequences, served length]` float32: at every served position,
    how far the reference's logit of the served token lies below the
    reference's best.

    `ends` is {"embed", "norm_f", "head"}; `block_of(i)` gives block i's
    parameters, asked for once a layer and dropped before the next is
    asked for; the sequences go through a layer `rows` at a time.  The
    served token i was chosen from the logits at position prompt_len - 1
    + i, whose input is the prompt and the served tokens before it.
    `with_block(i, block)` is called for every **expert** layer with
    block i in float32 while it is held.  `with_state(i, state)` is
    called for every KDA layer with the reference's state [sequences, H,
    D, D] after the input of the **last served step**: the prompt and
    all served tokens but the last (the step that chose the last token
    read the one before it)."""
    first_expert = cfg.get("first_expert", 0)
    tokens = jnp.concatenate([prompt, served], axis=1)[:, :-1]
    start, count = prompt.shape[1] - 1, served.shape[1]
    ends = _f32(ends)

    def one_layer(i):
        @jax.jit
        def apply(block, x):
            with jax.default_matmul_precision("highest"):
                out, found = layer(cfg, i, block, x, first_expert)
            return out, found["state"]
        return apply

    @jax.jit
    def head_gaps(ends, x, served):
        with jax.default_matmul_precision("highest"):
            z = rms_norm(x[:, start:start + count], ends["norm_f"],
                         cfg["rms_norm_eps"]) @ ends["head"]
        picked = jnp.take_along_axis(z, served[..., None], axis=-1)
        return jnp.max(z, axis=-1) - picked[..., 0]

    cuts = range(0, tokens.shape[0], rows)
    xs = [ends["embed"][tokens[at:at + rows]] for at in cuts]
    applies = {}
    for i in range(cfg["num_hidden_layers"]):
        block = _f32(block_of(i))
        kind = (layer_type(cfg, i), "ffn_in" in block)  # a compile a kind
        if kind not in applies:
            applies[kind] = one_layer(i)
        found = [applies[kind](block, x) for x in xs]
        xs = [x for x, _ in found]
        if with_state is not None and found[0][1] is not None:
            with_state(i, jnp.concatenate([s for _, s in found]))
        if with_block is not None and "ffn_in" not in block:
            with_block(i, block)
        del block, found
    return jnp.concatenate([head_gaps(ends, x, served[at:at + rows])
                            for x, at in zip(xs, cuts)])
