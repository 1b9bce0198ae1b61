"""Plain float32 reference of one chip's share of the language model of
Keye-VL-2.0-30B-A3B (huggingface.co/Kwai-Keye/Keye-VL-2.0-30B-A3B,
`model_type` `KeyeVL2`): the full-sequence forward pass in
straightforward `jax.numpy`, no cache, no kernel and no gather, the
chooser a boolean mask over the full score matrix, every key/value head
repeated for its group of query heads, every held expert applied densely
to every token, highest matmul precision, nothing imported from the
program.  The vision tower is not here (the catalog's row gives it no
width): what it would hand the language model, a vector a token at image
positions, is an input.

The model.  A token has a *slot* s (its index in the sequence: what a
cache is addressed by, and what "before" means) and a three-part rotary
*position* p = (p^t, p^h, p^w) (`mrope_section`, Qwen2-VL,
arXiv:2409.12191).  A text token has p^t = p^h = p^w; an image of h x w
tokens that starts at position p0 gives its token at (r, c) the position
(p0, p0 + r, p0 + c), and the token after it has p0 + max(h, w): after
an image the position lags the slot (`layout`).  With x [seq, hidden]
the float32 residual stream, RMSNorms N with a scale each, no bias
anywhere, a pre-norm block:

    a = x + Attn_l(N_in(x))
    y = a + F_l(N_pre_mlp(a))

Attn_l, for u = N_in(x), 32 query heads and 4 key/value heads of 128:

    q = u W_q, k = u W_k, v = u W_v, split into heads
    q, k: RMSNorm over each head's 128 values, one learned [128] scale
          for q and one for k a layer      (*assumed*: the Qwen3 block)
    q, k = mrope(q, p), mrope(k, p): rotate-half over a head's 64 pairs,
          pair i by the angle p^{c(i)} theta^(-i / 64), c(i) = t for
          i < 16, h for 16 <= i < 40, w for 40 <= i
    the chooser (`sa_config`; the lightning indexer of DeepSeek-V3.2
    with its queries projected from u, since this model has no query
    latent):
        q^I = u W_q^I (16 heads of 64), k^I = LayerNorm(u W_k^I) (64),
        w = u W_w (16); q^I and k^I are turned by p^t alone over their
        32 pairs                                          (*assumed*)
        I(t, s) = 16^(-1/2) 64^(-1/2) sum_j w_j relu(q^I_t,j . k^I_s)
        S_t = of the slots s <= t the `topk` with the largest I (all of
        them while t < topk)
    query head j reads key/value head j // 8
    o_j = sum_{s in S_t} softmax_s(q_j . k_s / sqrt(128)) v_s
    Attn = [o_0 .. o_31] W_o

F_l (every layer is an expert layer, no shared expert):

    P = softmax(u W_r) over all `scored` experts, float32
    e_j, j < 8: the largest of P;  w_j = P_{e_j} / (sum_j P_{e_j} + 1e-20)
    F(u) = sum_j w_j W_down,e_j (silu(W_gate,e_j u) * W_up,e_j u)

of which a share holds the experts `held` = (first, count): the sum then
runs over the j whose e_j lies in first .. first + count - 1.  After the
last layer z = N_f(x) W_head over the rows of the vocabulary the share
holds.

`params`: {"embed" [vocab, hidden], "blocks": [{"input_norm", "wq"
[hidden, 32 * 128], "wk", "wv" [hidden, 4 * 128], "q_norm", "k_norm"
[128], "wo", "w_iq" [hidden, 16 * 64], "w_ik" [hidden, 64], "ik_norm",
"ik_norm_b" [64], "w_iw" [hidden, 16], "pre_mlp_norm", "router"
[hidden, scored], "w_gate", "w_up" [count, hidden, width], "w_down"
[count, width, hidden]}], "norm_f", "head" [hidden, vocab]}, matrices
as [in, out].  `cfg` has the source's keys ("sa_config",
"rope_scaling", ...).  `cfg["control"]` (a dict, absent in every
configuration) makes the reference wrong on purpose, for the checks
that have to see it: {"swap_hw": true} has the height section of q and
k read the width position and the width section the height.

Everything below works on one sequence; `forward` maps it over a batch.
`session`, `gaps` and `held_part_off` are what the benchmark's cell asks
of it (benchmark/reference/keye_vl2.py is a copy of this file): a layer's
parameters held one at a time, queries in blocks.
"""

import math

import jax
import jax.numpy as jnp
import numpy as np


def _control(cfg, name, default):
    return cfg.get("control", {}).get(name, default)


def rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(
        jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps) * scale


def layer_norm(x, scale, bias, eps):
    centred = x - jnp.mean(x, axis=-1, keepdims=True)
    return centred * jax.lax.rsqrt(
        jnp.mean(jnp.square(centred), axis=-1, keepdims=True) + eps) \
        * scale + bias


def layout(length, spans=(), start=0):
    """(positions int32 [3, length], the rotary position after the last
    token) of `length` consecutive slots of which `spans` = [(slot, h,
    w)] are images of h x w tokens each, row after row, the rest text;
    the first slot has rotary position `start`."""
    out = np.zeros((3, length), np.int64)
    at, p = 0, start
    for slot, h, w in sorted(spans):
        if slot < at or slot + h * w > length:
            raise ValueError("spans %s overlap or leave %d slots"
                             % (spans, length))
        out[:, at:slot] = p + np.arange(slot - at)
        p += slot - at
        rows, cols = np.divmod(np.arange(h * w), w)
        out[:, slot:slot + h * w] = p + np.stack(
            [np.zeros_like(rows), rows, cols])
        p, at = p + max(h, w), slot + h * w
    out[:, at:] = p + np.arange(length - at)
    return out.astype(np.int32), int(p + length - at)


def mrope(x, positions, theta, sections):
    """x [seq, heads, dim] turned at `positions` [3, seq] (rotate-half:
    x cos + rotate_half(x) sin, the two halves of a head paired), pair i
    by the component its section names."""
    half = x.shape[-1] // 2
    inv_freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    component = np.repeat(np.arange(3), sections)          # [half]
    angles = positions.astype(jnp.float32)[component].T[:, None, :] \
        * inv_freq
    angles = jnp.concatenate([angles, angles], axis=-1)
    x1, x2 = x[..., :half], x[..., half:]
    rotated = jnp.concatenate([-x2, x1], axis=-1)
    return x * jnp.cos(angles) + rotated * jnp.sin(angles)


def _sections(cfg):
    t, h, w = cfg["rope_scaling"]["mrope_section"]
    if 2 * (t + h + w) != cfg["head_dim"]:
        raise ValueError("mrope_section %s does not add up to the %d pairs "
                         "of a head" % ([t, h, w], cfg["head_dim"] // 2))
    return t, h, w


def _components(cfg, positions):
    """`positions` [3, seq] as q and k read them: under the control
    `swap_hw` the height section reads the width and the width section
    the height."""
    return positions[jnp.asarray([0, 2, 1])] \
        if _control(cfg, "swap_hw", False) else positions


def cached(cfg, block, h, positions):
    """(k [seq, kv heads, dim] normed and turned, v [seq, kv heads, dim],
    k^I [seq, index dim] normed and turned) of h [seq, hidden]: what the
    three caches of the layer hold."""
    eps, dim = cfg["rms_norm_eps"], cfg["head_dim"]
    kv_heads, theta = cfg["num_key_value_heads"], float(cfg["rope_theta"])
    seq = h.shape[0]
    k = rms_norm((h @ block["wk"]).reshape(seq, kv_heads, dim),
                 block["k_norm"], eps)
    k = mrope(k, _components(cfg, positions), theta, _sections(cfg))
    v = (h @ block["wv"]).reshape(seq, kv_heads, dim)
    k_i = layer_norm(h @ block["w_ik"], block["ik_norm"],
                     block["ik_norm_b"], eps)
    # the chooser's keys: the temporal component for every pair
    return k, v, mrope(k_i[:, None, :], positions, theta,
                       (k_i.shape[-1] // 2, 0, 0))[:, 0]


def index_scores(cfg, block, h, positions, k_i):
    """I [queries, keys] for the queries h [queries, hidden] at
    `positions` [3, queries] over all the keys `k_i` (no mask)."""
    sa = cfg["sa_config"]
    heads, dim = sa["indexer_num_heads"], sa["indexer_head_dim"]
    q = mrope((h @ block["w_iq"]).reshape(-1, heads, dim), positions,
              float(cfg["rope_theta"]), (dim // 2, 0, 0))
    w = (h @ block["w_iw"]) * heads ** -0.5 * dim ** -0.5
    return jnp.einsum("qh,qhs->qs", w,
                      jax.nn.relu(jnp.einsum("qhd,sd->qhs", q, k_i)))


def kth_largest(x, k):
    """[rows, 1]: the k-th largest of every row of x [rows, n] float32
    (its smallest where n < k), -inf among the values: found a bit at a
    time over the order-preserving integer of a float32, no sort."""
    bits = jax.lax.bitcast_convert_type(x, jnp.int32)
    order = jnp.where(bits < 0, bits ^ jnp.int32(0x7FFFFFFF), bits)
    key = jax.lax.bitcast_convert_type(order, jnp.uint32) \
        ^ jnp.uint32(0x80000000)
    k = min(k, x.shape[-1])

    def grow(i, found):
        trial = found | (jnp.uint32(1) << (31 - i).astype(jnp.uint32))
        enough = jnp.sum(key >= trial, axis=-1, keepdims=True) >= k
        return jnp.where(enough, trial, found)

    found = jax.lax.fori_loop(
        0, 32, grow, jnp.zeros(x.shape[:-1] + (1,), jnp.uint32))
    order = jax.lax.bitcast_convert_type(found ^ jnp.uint32(0x80000000),
                                         jnp.int32)
    return jax.lax.bitcast_convert_type(
        jnp.where(order < 0, order ^ jnp.int32(0x7FFFFFFF), order),
        jnp.float32)


def choose(scores, top_k, q_slots):
    """The boolean mask [queries, keys] of the slots each query attends:
    of the keys s <= its slot, the min(top_k, slot + 1) with the largest
    score; of equal scores at the edge of the set (a score of exactly 0,
    where no head's product is positive) the earlier slots."""
    keys = scores.shape[-1]
    causal = jnp.arange(keys)[None, :] <= q_slots[:, None]
    live = jnp.where(causal, scores, -jnp.inf)
    kth = kth_largest(live, top_k)
    above = live > kth
    equal = causal & (live == kth)
    room = min(top_k, keys) - jnp.sum(above, axis=-1, keepdims=True)
    return above | (equal & (jnp.cumsum(equal, axis=-1) <= room))


def attend(cfg, block, h, positions, k, v, mask):
    """Attention of the queries h [queries, hidden] at `positions` [3,
    queries] over the keys k and values v [keys, kv heads, dim] under
    `mask` [queries, keys], through W_o."""
    heads, dim = cfg["num_attention_heads"], cfg["head_dim"]
    q = rms_norm((h @ block["wq"]).reshape(-1, heads, dim),
                 block["q_norm"], cfg["rms_norm_eps"])
    q = mrope(q, _components(cfg, positions), float(cfg["rope_theta"]),
              _sections(cfg))
    group = heads // k.shape[1]
    k_all, v_all = (jnp.repeat(t, group, axis=1) for t in (k, v))
    scores = jnp.einsum("qhd,khd->hqk", q, k_all) / math.sqrt(dim)
    scores = jnp.where(mask[None], scores, -jnp.inf)
    out = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(scores, axis=-1),
                     v_all)
    return out.reshape(out.shape[0], -1) @ block["wo"]


def route(cfg, block, u, indices=None):
    """(weights [tokens, scored], indices [tokens, top_k]) of u [tokens,
    hidden]: a token's weight of each scored expert, 0 where it is not
    among its chosen (the reference's own, or `indices` where a caller
    hands it a routing, weighted by the reference's probabilities)."""
    probs = jax.nn.softmax(u @ block["router"], axis=-1)
    if indices is None:
        indices = jax.lax.top_k(probs, cfg["num_experts_per_tok"])[1]
    top = jnp.take_along_axis(probs, indices, axis=1)
    if cfg["norm_topk_prob"]:
        top = top / (jnp.sum(top, axis=-1, keepdims=True) + 1e-20)
    hot = indices[..., None] == jnp.arange(probs.shape[-1])
    return jnp.sum(jnp.where(hot, top[..., None], 0.0), axis=1), indices


def routed(cfg, block, u, first=0, indices=None):
    """(F(u)'s held part for u [tokens, hidden], the experts chosen):
    every held expert applied to every token, one after another (a
    scan), weighted by the token's weight of it.  There is no shared
    expert: this is the whole feed-forward of a share."""
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


def _blocks(x, size, axis=0):
    """x [n, ...] as [n / size, size, ...] (along `axis`, moved first)."""
    x = jnp.moveaxis(x, axis, 0)
    x = x.reshape((x.shape[0] // size, size) + x.shape[1:])
    return x if axis == 0 else jnp.moveaxis(x, 1, axis + 1)


def layer(cfg, block, x, positions, first=0, query_block=None, last=None,
          start=0, full=False):
    """One decoder layer over one sequence x [seq, hidden] at
    `positions` [3, seq], seq - start a multiple of `query_block` (the
    whole of it where None): {"y": its output [seq - start, hidden],
    "attn": the attention sub-layer's, "k", "v", "k_i": what `cached`
    gives over every position, "indices": the experts chosen [seq -
    start, top_k], "found": what `last` asks for}, and with `full` the
    chosen sets as a mask "selection" [seq - start, seq].

    `start`: the outputs are wanted from that slot on only.  A slot's
    output reads nothing after it, so a caller that has kept the layers'
    inputs of a prefix (`session`'s `keep`) continues from there: every
    position's keys, values and index keys are made as before, the
    attention and the feed-forward run for the queries from `start` on.

    `last` = (at, h [hidden], selected [top_k] int32, live): the slot of
    a decode step, the normed input the program's attention sub-layer
    had there, the slots its chooser picked and how many of them are
    live.  "found" is then what the reference makes of that same input
    at that slot (its own float32 weights and arithmetic, its own caches
    of the slots before, the slot `at` itself made from `h`): (how many
    of the min(top_k, at + 1) slots it would choose are among the
    program's, as a share; the attention sub-layer's output [hidden]
    over the program's set).  No upstream layer's drift is in either."""
    eps, top_k = cfg["rms_norm_eps"], cfg["sa_config"]["topk"]
    seq = x.shape[0]
    query_block = query_block or seq - start
    slots = jnp.arange(seq)
    h = rms_norm(x, block["input_norm"], eps)
    k, v, k_i = cached(cfg, block, h, positions)

    def some_queries(part):
        h_b, p_b, at = part
        mask = choose(index_scores(cfg, block, h_b, p_b, k_i), top_k, at)
        return attend(cfg, block, h_b, p_b, k, v, mask), \
            mask if full else None

    o, chosen = jax.lax.map(some_queries, (
        _blocks(h[start:], query_block),
        _blocks(positions[:, start:], query_block, axis=1),
        _blocks(slots[start:], query_block)))
    o = o.reshape(seq - start, -1)
    a = x[start:] + o
    u = rms_norm(a, block["pre_mlp_norm"], eps)
    tokens = math.gcd(seq - start, 2048)
    f, indices = jax.lax.map(lambda part: routed(cfg, block, part, first),
                             _blocks(u, tokens))
    out = {"y": a + f.reshape(seq - start, -1), "attn": o, "k": k, "v": v,
           "k_i": k_i, "found": None,
           "indices": indices.reshape(seq - start, -1)}
    if full:
        out["selection"] = chosen.reshape(seq - start, seq)
    if last is not None:
        at, h_1, selected, live = last
        one, h_1 = jnp.reshape(at, (1,)), h_1[None]
        p_1 = jax.lax.dynamic_slice_in_dim(positions, at, 1, axis=1)
        k_1, v_1, k_i_1 = cached(cfg, block, h_1, p_1)
        put = jax.lax.dynamic_update_slice_in_dim
        own = choose(index_scores(cfg, block, h_1, p_1,
                                  put(k_i, k_i_1, at, 0)), top_k, one)[0]
        handed = jnp.zeros((seq,), bool).at[
            jnp.where(jnp.arange(selected.shape[0]) < live, selected,
                      seq)].set(True, mode="drop")
        out["found"] = (jnp.sum(own & handed) / jnp.sum(own), attend(
            cfg, block, h_1, p_1, put(k, k_1, at, 0), put(v, v_1, at, 0),
            handed[None])[0])
    return out


def _f32(tree):
    return jax.tree_util.tree_map(lambda a: jnp.asarray(a, jnp.float32),
                                  tree)


def embed(ends, tokens, vectors=None, image_slots=None):
    """x [seq, hidden]: the tokens' embeddings, and at the slots
    `image_slots` [n] the `vectors` [n, hidden] a tower would have
    given."""
    x = ends["embed"][jnp.asarray(tokens)]
    if vectors is None:
        return x
    return x.at[jnp.asarray(image_slots)].set(
        jnp.asarray(vectors, jnp.float32))


def forward(cfg, params, tokens, positions=None, vectors=None,
            image_slots=None, held=None, query_block=None):
    """{"logits" [batch, seq, vocab], "hidden": [L] each layer's output,
    "attn": [L] each attention sub-layer's output, "indices": [L] the
    experts chosen [batch, seq, top_k], "keys", "values", "index_keys":
    [L] what the caches would hold, "selection": [L] the chosen sets as
    masks [batch, seq, seq]} for local token ids `tokens` [batch, seq] at
    `positions` [3, batch, seq] (text where None), with `vectors` [batch,
    n, hidden] in the embedding's place at the slots `image_slots` [batch,
    n]; `held` = (first, count) says which of the scored experts
    `params` holds (default: the first `w_gate.shape[0]`)."""
    params = _f32(params)
    tokens = np.asarray(tokens)
    batch, seq = tokens.shape
    if positions is None:
        positions = np.broadcast_to(layout(seq)[0][:, None],
                                    (3, batch, seq))
    positions = jnp.asarray(positions)
    first = 0
    if held is not None:
        first = held[0]
        for block in params["blocks"]:
            if block["w_gate"].shape[0] != held[1]:
                raise ValueError("params hold %d experts, `held` says %d"
                                 % (block["w_gate"].shape[0], held[1]))
    names = {"hidden": "y", "attn": "attn", "indices": "indices",
             "keys": "k", "values": "v", "index_keys": "k_i",
             "selection": "selection"}
    out = {name: [] for name in names}
    with jax.default_matmul_precision("highest"):
        xs = [embed(params, tokens[b],
                    None if vectors is None else vectors[b],
                    None if vectors is None else image_slots[b])
              for b in range(batch)]
        for block in params["blocks"]:
            made = [layer(cfg, block, x, positions[:, b], first,
                          query_block, full=True)
                    for b, x in enumerate(xs)]
            xs = [m["y"] for m in made]
            for name, key in names.items():
                out[name].append(jnp.stack([m[key] for m in made]))
        out["logits"] = rms_norm(jnp.stack(xs), params["norm_f"],
                                 cfg["rms_norm_eps"]) @ params["head"]
    return out


def held_part_off(cfg, block, probe):
    """How far the held experts' part a step served lies from the
    reference's: `probe` is {"in": the routed layer's input [rows, 1,
    hidden], "idx": the experts the step's router chose [rows, top_k],
    "out": what its held experts gave for them [rows, 1, hidden]} as the
    step computed them; the reference's routed sum of the same input
    under the same choice (its own float32 probabilities of it, its own
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


def _layers(cfg, embedded, block_of, each):
    """The sequences whose embeddings are `embedded` ([n] x [seq,
    hidden]) through every layer, one layer's parameters held at a time:
    `each(i, block, row, x)` is called for every layer and sequence with
    the layer's input and gives its output; the last layer's come back."""
    xs = list(embedded)
    for i in range(cfg["num_hidden_layers"]):
        block = _f32(block_of(i))
        for row, x in enumerate(xs):
            xs[row] = each(i, block, row, x)
        del block
    return xs


def session(cfg, ends, block_of, documents, positions, vectors,
            image_slots, query_block, keep=()):
    """([layers] x (keys [documents, kv heads, seq, dim], values the
    same, index keys [documents, seq, index dim]), {document: [layers] x
    [seq, hidden]}) in float32, on the host: the caches a prefill pool
    would hand over for the seeded `documents` [documents, seq] at
    `positions` [3, documents, seq] with the tower's `vectors`
    [documents, n, hidden] at the slots `image_slots` [documents, n], for
    their
    caller to round once to the caches' types; and, for the documents
    `keep` names, the input every layer had, from which `gaps` continues
    a sequence that starts with that document (`prefix`).  The last
    layer's caches need its input alone: its attention and feed-forward
    are not run."""
    first = cfg.get("first_expert", 0)
    layers = cfg["num_hidden_layers"]
    ends = _f32(ends)
    positions = jnp.asarray(positions)

    @jax.jit
    def one(block, x, p):
        with jax.default_matmul_precision("highest"):
            out = layer(cfg, block, x, p, first, query_block)
        return out["y"], out["k"], out["v"], out["k_i"]

    @jax.jit
    def caches_alone(block, x, p):
        with jax.default_matmul_precision("highest"):
            h = rms_norm(x, block["input_norm"], cfg["rms_norm_eps"])
            return (x,) + cached(cfg, block, h, p)

    kept = [([], [], []) for _ in range(layers)]
    inputs = {int(d): [] for d in keep}

    def each(i, block, row, x):
        if row in inputs:
            inputs[row].append(np.asarray(jax.device_get(x)))
        y, k, v, k_i = (one if i < layers - 1 else caches_alone)(
            block, x, positions[:, row])
        kept[i][0].append(np.asarray(k).transpose(1, 0, 2))
        kept[i][1].append(np.asarray(v).transpose(1, 0, 2))
        kept[i][2].append(np.asarray(k_i))
        return y

    _layers(cfg, [embed(ends, documents[d], vectors[d], image_slots[d])
                  for d in range(len(documents))], block_of, each)
    return [tuple(np.stack(part) for part in parts) for parts in kept], \
        inputs


def gaps(cfg, ends, block_of, tokens, positions, start, served,
         query_block, last=None, with_block=None, prefix=None):
    """(`[sequences, served length]` float32: at every served position,
    how far the reference's logit of the served token lies below the
    reference's best; and per layer what `layer` finds for `last`).

    `tokens` [sequences, seq] are document, question and served tokens of
    each checked row at `positions` [3, sequences, seq]; served token i
    was chosen from the logits at slot start + i.  `ends` is {"embed",
    "norm_f", "head"}; `block_of(i)` gives block i's parameters, asked
    for once a layer and dropped before the next.  `last` = {"at",
    "live", "attn_in": [layers] x [sequences, hidden], "selected":
    [layers] x [sequences, top_k]} describes the call's last step for
    the same rows (`layer`'s `last`); the second result is then
    {"shared": [layers] x [sequences], "attn": [layers] x [sequences,
    hidden]}.  `with_block(i, block)` is called with block i in float32
    while it is held.  `prefix` = [sequences] x [layers] x [n, hidden]:
    every layer's input over a sequence's first n slots as `session` kept
    it (n <= start, the same for all, and no image after them); the
    forward then runs for the slots after them alone (`layer`'s
    `start`)."""
    first = cfg.get("first_expert", 0)
    count = served.shape[1]
    ends = _f32(ends)
    positions = jnp.asarray(positions)
    found = {"shared": [[] for _ in range(cfg["num_hidden_layers"])],
             "attn": [[] for _ in range(cfg["num_hidden_layers"])]}
    known = 0 if prefix is None else prefix[0][0].shape[0]

    @jax.jit
    def one(block, x, p, last):
        with jax.default_matmul_precision("highest"):
            out = layer(cfg, block, x, p, first, query_block, last, known)
        return out["y"], out["found"]

    def each(i, block, row, x):
        handed = None if last is None else (
            jnp.asarray(last["at"], jnp.int32),
            jnp.asarray(last["attn_in"][i][row], jnp.float32),
            jnp.asarray(last["selected"][i][row], jnp.int32),
            jnp.asarray(last["live"], jnp.int32))
        if known:
            x = jnp.concatenate([jnp.asarray(prefix[row][i]), x])
        y, got = one(block, x, positions[:, row], handed)
        if got is not None:
            found["shared"][i].append(float(got[0]))
            found["attn"][i].append(jax.device_get(got[1]))
        if with_block is not None and row == 0:
            with_block(i, block)
        return y

    xs = _layers(cfg, [embed(ends, row[known:]) for row in
                       np.asarray(tokens)], block_of, each)
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
