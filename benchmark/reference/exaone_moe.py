"""What the long-session cell's served tokens are held to: the plain
float32 reference of one chip's share of K-EXAONE-236B-A23B, a copy of
paddle_tpu/models/reference/exaone_moe.py's equations (see there: the
full-sequence forward, masked attention with the window as a mask, no
cache and no kernel, q and k normed head by head, rotary positions on
the window layers alone, the biased sigmoid router, every held expert
applied densely to every token, highest matmul precision, nothing from
the program; what config.json does not say is marked *assumed* there and
listed in the configuration file), applied a layer at a time, a sequence
at a time, a *turn* of positions at a time: at 32,768 positions one
sequence's [heads, T, T] scores would be 275 GB, so a layer's forward
runs over `turn` positions an application (the question and the served
tokens of a call: 1024), against the float32 keys and values of every
position before them, and its queries go `query_block` at a time over a
full layer's whole extent (a window layer's queries read the 128
positions before the turn and the turn: one small product).  A position's
output reads nothing after it, so the turns of a sequence one after
another are the full forward, sum for sum.  An expert layer is 1.9 GB in
float32, so `session` and `gaps` ask their caller for one layer's
parameters at a time and let go of them before the next.

One compiled function a kind of layer (window and dense, window and
sparse, full and sparse) serves both things made here: `session`, the
caches a decode-pool chip is handed (a full layer's keys and values over
the whole document, a window layer's last `sliding_window`, which a
prefill pool would have computed: 31 turns a document), and `gaps`, by
how much the reference's logit of each served token lies below the
reference's best at that position (0 where they agree: with seeded
weights the top two lie close often enough that rounding picks the other
one now and then, so the tokens themselves are not compared), one turn
more from the session's own float32 keys and values.  Beside the gaps,
for the call's last step, what the program's attention sub-layer is held
to (`turn`'s `last`), and `held_part_off` for its held experts.
"""

import math

import jax
import jax.numpy as jnp

WINDOW = "sliding_attention"


def rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(
        jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps) * scale


def rope(x, positions, theta):
    """x [seq, heads, dim] turned at `positions` [seq]: x cos +
    rotate_half(x) sin, the two halves of a head paired (*assumed*
    layout: the configuration file's `rope_layout`)."""
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


def projected(cfg, block, h, positions, kind):
    """(q [seq, heads, dim], k, v [seq, kv heads, dim]) of the normed
    input h [seq, hidden] at `positions`: q and k RMS-normed head by head
    (*assumed*: `qk_norm`) and, on a window layer alone, rotated
    (*assumed*: `rope_on_window_layers_only`).  k and v are what a cache
    of the layer holds."""
    heads, kv_heads = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    dim, eps = cfg["head_dim"], cfg["rms_norm_eps"]
    q = rms_norm((h @ block["wq"]).reshape(-1, heads, dim),
                 block["q_norm"], eps)
    k = rms_norm((h @ block["wk"]).reshape(-1, kv_heads, dim),
                 block["k_norm"], eps)
    v = (h @ block["wv"]).reshape(-1, kv_heads, dim)
    if kind == WINDOW:
        theta = float(cfg["rope_parameters"]["rope_theta"])
        q, k = rope(q, positions, theta), rope(k, positions, theta)
    return q, k, v


def attend(cfg, q, q_positions, k, v, k_positions, kind):
    """Attention of the queries q [n, heads, dim] at `q_positions` over
    the keys and values [m, kv heads, dim] at `k_positions` (a negative
    one holds nothing), query head j reading key/value head j // group:
    [n, heads * dim].  A key is seen where it is not after the query
    and, on a window layer, fewer than `sliding_window` before it
    (*assumed*: `window_edge`)."""
    kv_heads, dim = k.shape[1], q.shape[-1]
    grouped = q.reshape(q.shape[0], kv_heads, -1, dim)
    keep = (k_positions[None, :] <= q_positions[:, None]) \
        & (k_positions[None, :] >= 0)
    if kind == WINDOW:
        keep &= q_positions[:, None] - k_positions[None, :] \
            < cfg["sliding_window"]
    scores = jnp.einsum("qhgd,khd->hgqk", grouped, k) / math.sqrt(dim)
    scores = jnp.where(keep, scores, -jnp.inf)
    out = jnp.einsum("hgqk,khd->qhgd", jax.nn.softmax(scores, axis=-1), v)
    return out.reshape(q.shape[0], -1)


def route(cfg, block, u, indices=None):
    """(weights [tokens, scored], indices [tokens, top_k]) of u [tokens,
    hidden]: a token's weight of each scored expert, 0 where it is not
    among its chosen (the reference's own choice, by s + b, *assumed*:
    `router_bias`; or `indices` where a caller hands it a routing); the
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
    scan), weighted by the token's weight of it; what the absent experts
    would add is left out, as the program leaves it out."""
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


def turn(cfg, block, kind, x, start, k_before, v_before, first,
         query_block, last, caches_alone=False):
    """(y, k, v, attn) of one decoder layer of `kind` over the positions
    start .. start + n - 1 of one sequence, x [n, hidden] the layer's
    input there (*assumed* order: `block_order`, pre-norm).

    `k_before`, `v_before`: the layer's keys and values of the positions
    before `start`, float32.  A full layer's: [extent, kv heads, dim],
    position p in row p, rows from `start` on unread; `k`, `v` come back
    with the turn's written.  A window layer's: [window, kv heads, dim],
    the positions start - window .. start - 1 in order (those below 0
    hold nothing); `k`, `v` are the last `window` of them and the turn.

    `last` = (j, h [hidden]): the normed input the program's attention
    sub-layer had at position start + j; `attn` is what the reference
    makes of that same input there (its own float32 weights and
    arithmetic, its own keys and values of the positions before, the
    entry of start + j itself made from `h`): the sub-layer's output
    [hidden].  No upstream layer's drift is in what that one step was
    given; what the positions before it left in the caches is the
    reference's own.

    `caches_alone`: y is x as it came; the last layer's caches need its
    input alone."""
    eps, window = cfg["rms_norm_eps"], cfg["sliding_window"]
    n = x.shape[0]
    positions = start + jnp.arange(n)
    h = rms_norm(x, block["input_norm"], eps)
    q, k, v = projected(cfg, block, h, positions, kind)
    put = jax.lax.dynamic_update_slice_in_dim
    if kind == WINDOW:
        keys = jnp.concatenate([k_before, k])
        values = jnp.concatenate([v_before, v])
        at = jnp.concatenate([start - window + jnp.arange(window),
                              positions])
        k_after, v_after, offset = keys[-window:], values[-window:], window
    else:
        keys, values = put(k_before, k, start, 0), put(v_before, v, start, 0)
        at = jnp.arange(keys.shape[0])
        k_after, v_after, offset = keys, values, start
    if caches_alone:
        return x, k_after, v_after, jnp.zeros_like(x[0])

    def some_queries(part):
        return attend(cfg, part[0], part[1], keys, values, at, kind)

    o = jax.lax.map(some_queries, (_blocks(q, query_block),
                                   _blocks(positions, query_block)))
    a = x + o.reshape(n, -1) @ block["wo"]
    u = rms_norm(a, block["pre_mlp_norm"], eps)
    f = jax.lax.map(lambda part: feed_forward(cfg, block, part, first),
                    _blocks(u, math.gcd(n, 2048)))
    j, h_1 = last
    one = jnp.reshape(start + j, (1,))
    q_1, k_1, v_1 = projected(cfg, block, h_1[None], one, kind)
    attn = attend(cfg, q_1, one, put(keys, k_1, offset + j, 0),
                  put(values, v_1, offset + j, 0), at, kind)[0] @ block["wo"]
    return a + f.reshape(n, -1), k_after, v_after, attn


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
        # no row of the step chose a held expert (8 rows x 8 choices put
        # 4 assignments on the held 8 in the mean: none in one layer in
        # fifty): the part is zero on both sides, or the step put
        # something where nothing belongs
        return 0.0 if off == 0.0 else float("inf")
    return (off / size) ** 0.5


def _f32(tree):
    return jax.tree_util.tree_map(lambda a: jnp.asarray(a, jnp.float32),
                                  tree)


class Layers:
    """The compiled `turn` of every kind of layer, one a (kind, dense or
    sparse, caches alone) that is asked for: `session` and `gaps` of one
    run share them."""

    def __init__(self, cfg, query_block):
        self.cfg, self.query_block = cfg, query_block
        self.first = cfg.get("first_expert", 0)
        self._made = {}

    def __call__(self, i, block, x, start, k_before, v_before, last=None,
                 caches_alone=False):
        cfg = self.cfg
        kind = cfg["layer_types"][i]
        key = (kind, "ffn_in" in block, caches_alone)
        if key not in self._made:
            def one(block, x, start, k_before, v_before, last):
                with jax.default_matmul_precision("highest"):
                    return turn(cfg, block, kind, x, start, k_before,
                                v_before, self.first, self.query_block,
                                last, caches_alone)
            self._made[key] = jax.jit(one)
        if last is None:    # nobody reads `attn`: position 0, zeros
            last = (jnp.int32(0), jnp.zeros((x.shape[1],), jnp.float32))
        return self._made[key](block, x, jnp.int32(start), k_before,
                               v_before, last)

    def nothing_before(self, i):
        """A layer's keys (or values) of no position at all."""
        cfg = self.cfg
        slots = cfg["sliding_window"] \
            if cfg["layer_types"][i] == WINDOW else cfg["serve_positions"]
        return jnp.zeros((slots, cfg["num_key_value_heads"],
                          cfg["head_dim"]), jnp.float32)


def session(cfg, layers, ends, block_of, documents, size, keep=()):
    """([layers] x (keys, values): [documents, kv heads, slots, dim]
    float32 on the host, as the step's caches lie: a full layer's over
    the document, position p in slot p of `serve_positions`; a window
    layer's the last `sliding_window` positions, position p in slot p
    mod `sliding_window`; and {document: [layers] x (keys, values)} as
    `turn` takes them, for the documents `keep` names, from which `gaps`
    continues a sequence that starts with that document).  The caches a
    prefill pool would hand over for the seeded `documents` [documents,
    seq], seq a multiple of `size`, for their caller to round once to
    the caches' types.  The last layer's caches need its input alone:
    its attention and feed-forward are not run."""
    import numpy as np

    count, seq = documents.shape
    depth, window = cfg["num_hidden_layers"], cfg["sliding_window"]
    if seq % size or (seq and seq < window):
        raise ValueError("a session of %d positions is not whole turns of "
                         "%d, or shorter than the window" % (seq, size))
    ends = _f32(ends)
    xs = [[ends["embed"][jnp.asarray(documents[d, at:at + size])]
           for at in range(0, seq, size)] for d in range(count)]
    made, kept = [], {int(d): [] for d in keep}
    for i in range(depth):
        block = _f32(block_of(i))
        ring = cfg["layer_types"][i] == WINDOW
        held = ([], [])
        for d in range(count):
            k = v = layers.nothing_before(i)
            for t in range(seq // size):
                xs[d][t], k, v, _ = layers(
                    i, block, xs[d][t], t * size, k, v,
                    caches_alone=i == depth - 1)
            if d in kept:
                kept[d].append((np.asarray(k), np.asarray(v)))
            for out, value in zip(held, (k, v)):
                value = np.asarray(value).transpose(1, 0, 2)
                if ring:    # positions seq - window .. seq - 1, in order
                    value = np.roll(value, seq % window, axis=1)
                out.append(value)
        made.append((np.stack(held[0]), np.stack(held[1])))
        del block
    return made, kept


def gaps(cfg, layers, ends, block_of, tokens, start, first_logit, served,
         last=None, with_block=None, before=None):
    """(`[sequences, served length]` float32: at every served position,
    how far the reference's logit of the served token lies below the
    reference's best; and {"attn": [layers] x [sequences, hidden]}, what
    `turn` finds for `last`).

    `tokens` [sequences, n] are the question and the served tokens of
    each checked row, at positions start .. start + n - 1; served token
    i was chosen from the logits at position start + first_logit + i.
    `before` = [sequences] x [layers] x (keys, values) as `session` kept
    them: what lies before `start`.  `ends` is {"embed", "norm_f",
    "head"}; `block_of(i)` gives block i's parameters, asked for once a
    layer and dropped before the next.  `last` = {"at": the position of
    the call's last step, "attn_in": [layers] x [sequences, hidden]}.
    `with_block(i, block)` is called with block i in float32 while it is
    held."""
    count = served.shape[1]
    ends = _f32(ends)
    depth = cfg["num_hidden_layers"]
    found = {"attn": [[] for _ in range(depth)]}
    xs = [ends["embed"][jnp.asarray(row)] for row in tokens]
    for i in range(depth):
        block = _f32(block_of(i))
        for row in range(len(xs)):
            handed = None if last is None else (
                jnp.int32(last["at"] - start),
                jnp.asarray(last["attn_in"][i][row], jnp.float32))
            k, v = before[row][i]
            xs[row], _, _, attn = layers(i, block, xs[row], start,
                                         jnp.asarray(k), jnp.asarray(v),
                                         handed)
            found["attn"][i].append(jax.device_get(attn))
        if with_block is not None:
            with_block(i, block)
        del block

    @jax.jit
    def head_gaps(ends, x, served):
        with jax.default_matmul_precision("highest"):
            z = rms_norm(x[first_logit:first_logit + count], ends["norm_f"],
                         cfg["rms_norm_eps"]) @ ends["head"]
        picked = jnp.take_along_axis(z, served[:, None], axis=-1)
        return jnp.max(z, axis=-1) - picked[:, 0]

    return jnp.stack([head_gaps(ends, x, jnp.asarray(row))
                      for x, row in zip(xs, served)]), found
