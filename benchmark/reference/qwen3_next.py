"""Plain float32 reference of one chip's share of Qwen3-Next-80B-A3B
(huggingface.co/Qwen/Qwen3-Next-80B-A3B-Instruct, `model_type`
`qwen3_next`): the full-sequence forward pass in straightforward
`jax.numpy`, the gated delta rule **position by position** (`lax.scan`
over the sequence: no chunk, no state handed in, no kernel), full causal
attention over the whole sequence (no cache), every held expert applied
densely to every token and masked by the routing weights, highest matmul
precision, nothing imported from the program.

The model.  x [batch, seq, hidden]; N(x; w) = x / sqrt(mean(x^2) + eps)
* w (the family stores w - 1; a scale here is the whole factor); no bias
anywhere; layer l is `full_attention` where (l + 1) %
`full_attention_interval` == 0, else `linear_attention`:

    a = x + mixer(N(x; w1))
    y = a + moe(N(a; w2))

Linear layer (Gated DeltaNet), u = N(x; w1), `linear_num_key_heads` Hk,
`linear_num_value_heads` H heads of `linear_key_head_dim` /
`linear_value_head_dim` values, value head j reads key head j // (H /
Hk):

    [q | k | v | z] = u W_qkvz;  [b | a] = u W_ba
    [q | k | v] = silu(conv([q | k | v]; F))    depthwise, causal, width
                                                `linear_conv_kernel_dim`
    q_h = q_h / sqrt(sum q_h^2 + 1e-6) / sqrt(key dim);  k_h likewise,
                                                          unscaled
    beta = sigmoid(b);  g = -exp(A_log) softplus(a + dt_bias)
    per value head, S [key dim, value dim] from zeros, position by
    position:  S = exp(g_t) S;  r = S^T k_t;
               S = S + k_t (beta_t (v_t - r))^T;  o_t = S^T q_t
    y_j = N(o_j; w_n) * silu(z_j);   mixer = concat_j(y_j) W_o

Full layer (gated attention), `num_attention_heads` heads over
`num_key_value_heads` key/value heads of `head_dim`, the first
`partial_rotary_factor` of a head rotated (rotate-half, `rope_theta`):

    [q | gate] = u W_q, head by head: a head's query values, then its
                 gate values;   k = u W_k;  v = u W_v
    q_h = rope(N(q_h; w_q));  k_h = rope(N(k_h; w_k))
    o_h = softmax_causal(q_h k_{h // group}^T / sqrt(head_dim)) v
    mixer = (concat_h(o_h) * sigmoid(gate)) W_o

Expert layer, s = N(a; w2):

    p = softmax(s W_r) over the `scored_experts`
    (p_j, e_j), j < `num_experts_per_tok`: the largest;
    w_j = p_j / sum_j p_j   (`norm_topk_prob`)
    moe = sigmoid(s w_sg) E_shared(s) + sum_j w_j E_{e_j}(s)

of which a share holds the experts first .. first + count - 1
(`first_expert`, and `w_gate.shape[0]` of them): the sum then runs over
the j whose e_j lies there (the shared expert is replicated, whole in
every share: `shared=False` leaves it out, for adding shares up).  After
the last layer z = N(x; w_f) W_head over the rows of the vocabulary the
share holds; token ids are local to them.

`params`: {"embed" [vocab, hidden], "blocks": [{"input_norm",
"pre_mlp_norm", "shared_in" [hidden, 2 * width] (gate columns first),
"shared_out", "shared_gate" [hidden, 1], "router" [hidden, scored],
"w_gate", "w_up" [count, hidden, width], "w_down" [count, width,
hidden], and for a linear layer "w_qkvz", "w_ba", "conv" [channels,
width], "a_log", "dt_bias" [H], "out_norm" [value dim], "wo", for a full
one "wq", "wk", "wv", "q_norm", "k_norm" [head_dim], "wo"}], "norm_f",
"head" [hidden, vocab]}, matrices as [in, out].  `cfg` has the source's
keys, and `scored_experts` and `first_expert` of a share.

`cfg["control"]`, where present, makes the reference **wrong** in one
named way (a check that `correct`'s limits refuse a program that
computes something else: benchmark/tests/state_control.py,
scripts/qwen3next_check.py): {"state": "bfloat16" | "zero"} rounds or
zeroes the state after every position, "decay": False leaves exp(g) out,
"beta": 1 takes beta as 1, "read": False leaves `S^T k` out (plain
linear attention), "tail_cut": p starts the convolution from zeros again
at position p, "rotary": n rotates the first n values of a head,
"attn_gate": False and "shared_gate": False leave a gate out, "drop":
True drops every token's last chosen expert.
"""

import math

import jax
import jax.numpy as jnp

LINEAR, FULL = "linear_attention", "full_attention"


def layer_type(cfg, index):
    return FULL if (index + 1) % cfg["full_attention_interval"] == 0 \
        else LINEAR


def _control(cfg, key, default):
    return (cfg.get("control") or {}).get(key, default)


def rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(
        jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps) * scale


def rope(x, positions, theta, rotary):
    """x [batch, seq, heads, dim] with the first `rotary` values of
    every head turned at `positions` [seq] (x cos + rotate_half(x) sin,
    the two halves of the rotated part paired); the rest pass."""
    inv_freq = 1.0 / theta ** (jnp.arange(0, rotary, 2, dtype=jnp.float32)
                               / rotary)
    angles = positions[:, None, None].astype(jnp.float32) * inv_freq
    angles = jnp.concatenate([angles, angles], axis=-1)
    turned, rest = x[..., :rotary], x[..., rotary:]
    x1, x2 = turned[..., :rotary // 2], turned[..., rotary // 2:]
    rotated = jnp.concatenate([-x2, x1], axis=-1)
    return jnp.concatenate(
        [turned * jnp.cos(angles) + rotated * jnp.sin(angles), rest],
        axis=-1)


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
    """(o [batch, seq, H, value dim], the state after the last position
    [batch, H, key dim, value dim]) of the gated delta rule position by
    position from a zero state: q, k [batch, seq, H, key dim] (normed,
    each value head's own copy of its key head), v [batch, seq, H, value
    dim], g and beta [batch, seq, H]."""
    kept = _control(cfg, "state", None)

    def step(s, at):
        q_t, k_t, v_t, g_t, b_t = at
        if _control(cfg, "decay", True):
            s = s * jnp.exp(g_t)[..., None, None]
        held = jnp.einsum("bhkv,bhk->bhv", s, k_t) \
            if _control(cfg, "read", True) else 0.0
        s = s + k_t[..., :, None] * (b_t[..., None]
                                     * (v_t - held))[..., None, :]
        out = jnp.einsum("bhkv,bhk->bhv", s, q_t)
        if kept == "bfloat16":
            # (an explicit rounding: XLA drops a cast down and up)
            s = jax.lax.reduce_precision(s, exponent_bits=8,
                                         mantissa_bits=7)
        elif kept == "zero":
            s = jnp.zeros_like(s)
        return s, out

    batch, _, heads, key_dim = q.shape
    state = jnp.zeros((batch, heads, key_dim, v.shape[-1]), jnp.float32)
    state, out = jax.lax.scan(
        step, state, tuple(jnp.moveaxis(t, 1, 0)
                           for t in (q, k, v, g, beta)))
    return jnp.moveaxis(out, 0, 1), state


def linear_mixer(cfg, block, h):
    """(the Gated DeltaNet mixer of h [batch, seq, hidden], the state
    after the last position)."""
    batch, seq, _ = h.shape
    key_heads, heads = (cfg["linear_num_key_heads"],
                        cfg["linear_num_value_heads"])
    key_dim, value_dim = (cfg["linear_key_head_dim"],
                          cfg["linear_value_head_dim"])
    key_width, value_width = key_heads * key_dim, heads * value_dim
    mixed = h @ block["w_qkvz"]
    qkv, z = mixed[..., :2 * key_width + value_width], \
        mixed[..., 2 * key_width + value_width:]
    b, a = jnp.split(h @ block["w_ba"], 2, axis=-1)
    qkv = jax.nn.silu(causal_conv(qkv, block["conv"],
                                  _control(cfg, "tail_cut", None)))
    q, k, v = (t.reshape(batch, seq, n, -1) for t, n in zip(
        jnp.split(qkv, [key_width, 2 * key_width], axis=-1),
        (key_heads, key_heads, heads)))

    def l2norm(t):
        return t * jax.lax.rsqrt(
            jnp.sum(jnp.square(t), axis=-1, keepdims=True) + 1e-6)

    group = heads // key_heads
    q = jnp.repeat(l2norm(q) / math.sqrt(key_dim), group, axis=2)
    k = jnp.repeat(l2norm(k), group, axis=2)
    beta = jax.nn.sigmoid(b)
    if _control(cfg, "beta", None) is not None:
        beta = jnp.full_like(beta, _control(cfg, "beta", None))
    g = -jnp.exp(block["a_log"]) * jax.nn.softplus(a + block["dt_bias"])
    o, state = delta_rule(cfg, q, k, v, g, beta)
    y = rms_norm(o, block["out_norm"], cfg["rms_norm_eps"]) \
        * jax.nn.silu(z.reshape(batch, seq, heads, value_dim))
    return y.reshape(batch, seq, -1) @ block["wo"], state


def full_mixer(cfg, block, h):
    """Gated attention of h [batch, seq, hidden] over the whole
    sequence."""
    batch, seq, _ = h.shape
    heads, kv_heads = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    dim, eps = cfg["head_dim"], cfg["rms_norm_eps"]
    rotary = _control(cfg, "rotary",
                      int(dim * cfg["partial_rotary_factor"]))
    positions = jnp.arange(seq)
    q, gate = jnp.split((h @ block["wq"]).reshape(batch, seq, heads,
                                                  2 * dim), 2, axis=-1)
    k = (h @ block["wk"]).reshape(batch, seq, kv_heads, dim)
    v = (h @ block["wv"]).reshape(batch, seq, kv_heads, dim)
    q = rope(rms_norm(q, block["q_norm"], eps), positions,
             cfg["rope_theta"], rotary)
    k = rope(rms_norm(k, block["k_norm"], eps), positions,
             cfg["rope_theta"], rotary)
    q = q.reshape(batch, seq, kv_heads, heads // kv_heads, dim)
    scores = jnp.einsum("bqhgd,bkhd->bhgqk", q, k) / math.sqrt(dim)
    causal = jnp.tril(jnp.ones((seq, seq), bool))
    probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
    out = jnp.einsum("bhgqk,bkhd->bqhgd", probs, v).reshape(
        batch, seq, heads, dim)
    if _control(cfg, "attn_gate", True):
        out = out * jax.nn.sigmoid(gate)
    return out.reshape(batch, seq, -1) @ block["wo"]


def route(cfg, block, u, indices=None):
    """(weights [tokens, scored], indices [tokens, top_k]) of u [tokens,
    hidden]: a token's weight of each scored expert, 0 where it is not
    among its `top_k` (the reference's own, or `indices` where given,
    weighted by the reference's probabilities of them)."""
    top_k = cfg["num_experts_per_tok"]
    probs = jax.nn.softmax(u @ block["router"], axis=-1)
    if indices is None:
        _, indices = jax.lax.top_k(probs, top_k)
    chosen = jnp.take_along_axis(probs, indices, axis=-1)
    if cfg["norm_topk_prob"]:
        chosen = chosen / (jnp.sum(chosen, axis=-1, keepdims=True) + 1e-20)
    if _control(cfg, "drop", False):
        chosen = chosen.at[:, -1].set(0.0)
    rows = jnp.arange(u.shape[0])[:, None]
    return jnp.zeros_like(probs).at[rows, indices].add(chosen), indices


def routed(cfg, block, u, first, indices=None):
    """(the held experts' part of the routed sum for u [tokens, hidden],
    the experts chosen): every held expert applied to every token,
    weighted by the routing weights (0 for a token that did not choose
    it)."""
    weights, indices = route(cfg, block, u, indices)
    count = block["w_gate"].shape[0]
    held = weights[:, first:first + count]
    act = jax.nn.silu(jnp.einsum("td,edf->etf", u, block["w_gate"])) \
        * jnp.einsum("td,edf->etf", u, block["w_up"])
    each = jnp.einsum("etf,efd->etd", act, block["w_down"])
    return jnp.einsum("te,etd->td", held, each), indices


def feed_forward(cfg, block, u, first, indices=None, shared=True):
    """(moe(u) for u [tokens, hidden], the experts chosen)."""
    out, indices = routed(cfg, block, u, first, indices)
    if shared:
        part = gated(u, block["shared_in"], block["shared_out"])
        if _control(cfg, "shared_gate", True):
            part = part * jax.nn.sigmoid(u @ block["shared_gate"])
        out = out + part
    return out, indices


def layer(cfg, index, block, x, first=0, indices=None, shared=True):
    """(the layer's output, {"mixer": the mixer's output, "state": a
    linear layer's state after the last position or None, "indices"})
    for x [batch, seq, hidden]."""
    eps = cfg["rms_norm_eps"]
    h = rms_norm(x, block["input_norm"], eps)
    if layer_type(cfg, index) == LINEAR:
        mixer, state = linear_mixer(cfg, block, h)
    else:
        mixer, state = full_mixer(cfg, block, h), None
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
    "mixer": [L] each mixer's output, "states": [L] a linear layer's
    state after the last position (None for a full layer), "indices":
    [L] the experts chosen [tokens, top_k]} for local token ids `tokens`
    [batch, seq]; `params` hold the experts from `cfg["first_expert"]`
    (default 0) on."""
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
    under the same choice (its own float32 probabilities of it) is what
    "out" is held to, as the root mean square of the difference over the
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
    `with_block(i, block)` is called with block i in float32 while it is
    held.  `with_state(i, state)` is called for every linear layer with
    the reference's state [sequences, H, key dim, value dim] after the
    input of the **last served step**: the prompt and all served tokens
    but the last (the step that chose the last token read the one before
    it)."""
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
        if layer_type(cfg, i) not in applies:   # one compile a kind
            applies[layer_type(cfg, i)] = one_layer(i)
        apply = applies[layer_type(cfg, i)]
        found = [apply(block, x) for x in xs]
        xs = [x for x, _ in found]
        if with_state is not None and found[0][1] is not None:
            with_state(i, jnp.concatenate([s for _, s in found]))
        if with_block is not None:
            with_block(i, block)
        del block, found
    return jnp.concatenate([head_gaps(ends, x, served[at:at + rows])
                            for x, at in zip(xs, cuts)])
