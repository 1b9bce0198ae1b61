"""A routed expert layer (mixture of experts) as two framework ops.

`moe_router` gives every token its probabilities over the experts, the
`top_k` it is sent to with their (not renormalised) probabilities, and
the two auxiliary losses of the layer (load balance, Fedus et al. 2021,
and the router z-loss, Zoph et al. 2022, as OLMoE, arXiv:2409.02060,
trains with).  All of it is float32 whatever the compute type: a
rounded logit sends a token to another expert.  Its gradient is the
generic one: jax.vjp reaches the weights through the chosen
probabilities and the two losses and never through the indices, which
are integers, and what it computes again is one [tokens, hidden] x
[hidden, experts] product.

`moe_experts` applies to every token all of its experts, with no
capacity and nothing dropped: the `tokens * top_k` assignments are
ordered by expert (a stable sort: static shapes, the group sizes an
[experts] array on the device), the tokens gathered into that order,
three grouped products (kernels/grouped_matmul.py) make
down(silu(gate(x)) * up(x)) for each row with its expert's weights, and
each token adds up its rows weighted by the router's probabilities.
Products take the compute type's operands (bfloat16 under AMP) and add
up in float32.

`moe_router` can also score as the DeepSeek-V3 family serves
(`scoring="sigmoid"`: each expert's sigmoid on its own; `norm_topk`
divides the chosen weights by their sum, `scale` multiplies them), with
no auxiliary loss: those two are the softmax router's.  And
`moe_experts` can hold a *range* of the experts the router scores, as
one chip of an expert-parallel deployment does: `WGate.shape[0]` experts
from `first_expert` on.  An assignment to an expert outside the range is
left out before the ordering (it sorts behind every held one and
belongs to no group, so no product visits it) and adds nothing: the
output is the held experts' part of the layer's, which the other chips'
parts would be added to.  A held expert that no token chose is not
visited either (the grouped row products walk the groups that have a
row), so a lightly loaded share reads the weights of the experts that
were chosen and no others.  Its gradient runs the six grouped products
over the held groups alone: an absent assignment's row gives the input
no gradient and its routing weight a gradient of 0 (its part of the
layer's output, and of every gradient, is another chip's), and the
weights' gradients are the held experts'.  The shares' outputs and
input gradients add up to the uncut layer's.

The stable order puts the held groups' rows first, so the first
sum(Counts) rows of every ordered array are the held ones, and an op
that holds an eighth of the experts scored needs an eighth of its rows,
or as many more as its routers send the range: how many is data on the
device.  Where a ranged op orders 32768 rows or more (`_chunk_rows`) the
row work between the grouped products therefore goes *by chunks of the
held rows*: each pass is a loop (`_over_held_chunks`) over chunks of
8192 rows of the order, a whole number of the grouped kernels' row
tiles, from row 0 as far as row sum(Counts), sum(Counts) // 8192 + 1
trips, so its work follows the rows the range holds and no bound stands
between two bodies; nothing is dropped or approximated whatever the
routers do.  A trip slices its chunk's rows out of the whole arrays,
works on them (the activation and its derivative; dOut gathered for the
chunk's rows; a token's sum of its held rows, `_held_token_sums`: Out
forward, X@GRAD backward, from a chunk's gathered rows in place of n *
k) and writes the results to their place in a whole array that the loop
carries in place; the chunk that holds row sum(Counts) masks the rows
past it, as the rows past the held groups always were masked, and the
rows past the last trip's chunk hold whatever the carried array started
with.  A new array starts as one that nothing has written
(`grouped_matmul.unwritten`: no pass over all rows is left, not even a
fill; what such rows hold reaches no sum), and where the results are a
grouped product's row operands made from arrays of the same shape that
nothing reads again (the backward's d_gate, d_up and h * w from Gate, Up
and the down product's gradient; the sum of the two input gradients over
the first of them and the rows' sums of it over the second) they are
written over those, with no array of their own.  The grouped products
stand outside the loops, once each, over whole arrays: a grouped product
reads and writes no row past the held groups' whatever its operands'
length (98304 rows for 24576 cost a row product 0.1 ms and a `gmm_dw`
0.5 on the v5e) but for the tile of row sum(Counts), where `gmm_dw`
gives a held expert that got no row its visit, and that tile lies in the
last trip's chunk.  The forward has two loops (the activation; after the
down product, the token sums) with the ordering, the gather of Xs and
the gate and up products before them (the kept outputs keep their n * k
rows); the backward four (dOut's rows; around the activation; the sum of
the gate's and the up's input gradients; the token sums) with the six
products between them.  The loops open the scope `moe_compact` inside
the op's three.  Every other op (no range, under 32768 rows, or no whole
number of chunks) has the one plain body over every row, where a loop's
fixed cost would not be paid back.

The experts' gate is SiLU, or with `activation="relu"` ReLU (ReGLU
experts), forward and backward.

The gradient of `moe_experts` is explicit, for the reason
`flash_attention`'s is: jax.vjp of the op would run the forward's
three grouped products again.  The forward op keeps, as outputs of its
own that the gradient op reads:

    RowSlot, TokenRow [tokens * top_k] int32    the order and its inverse
    Counts             [experts] int32          rows an expert
    Xs   [tokens * top_k, hidden]               the ordered input
    Gate, Up [tokens * top_k, expert width]     the two pre-activations

in the compute type: at 4096 tokens, top-8, 2048 -> 1024 in bfloat16
128 + 64 + 64 MiB a layer.  (Recomputing Gate and Up from Xs instead
would save the 128 MiB and cost two of the nine products of a step;
gathering Xs again would save 128 MiB for one more pass over it.)  The
down product's result is not kept: a routing weight's gradient is
<dOut_token, y_row> = <dOut_token @ w_down^T, h_row>, which the backward
has on its way to dh.  Six grouped products, none of them a forward one.
"""

import contextlib
import math

import jax
import jax.numpy as jnp

from ..obs import telemetry
from .amp_util import amp_result, mxu_operands
from .registry import register_grad_kernel, register_op


def _set_meta(block, name, shape, dtype):
    desc = block.var_recursive(name).desc
    desc.shape, desc.dtype, desc.lod_level = tuple(shape), dtype, 0


def _tokens(shape):
    """The rows of X [..., hidden]; -1 where the Program leaves an axis
    open."""
    return -1 if min(shape[:-1]) < 0 else math.prod(shape[:-1])


def _rows_an_expert(top_idx, experts):
    """[experts] int32: how many of the assignments fell on each expert.
    By comparison: a scatter-add of 32768 single elements costs ten
    times as much on the TPU."""
    return jnp.sum(
        top_idx.reshape(-1, 1) == jnp.arange(experts, dtype=top_idx.dtype),
        axis=0, dtype=jnp.int32)


def _router_infer_shape(block, op_desc):
    x = block.var_recursive(op_desc.input("X")[0]).desc
    experts = block.var_recursive(op_desc.input("W")[0]).desc.shape[1]
    n, k = _tokens(x.shape), int(op_desc.attrs["top_k"])
    for slot, shape, dtype in (
            ("Logits", (n, experts), "float32"),
            ("TopW", (n, k), "float32"), ("TopIdx", (n, k), "int32"),
            ("LbLoss", (1,), "float32"), ("ZLoss", (1,), "float32")):
        _set_meta(block, op_desc.output(slot)[0], shape, dtype)


def _chosen(top_w, attrs):
    """The chosen experts' weights as the op's attrs ask for them; as
    they are where neither is set."""
    if attrs.get("norm_topk", False):
        top_w = top_w / (jnp.sum(top_w, axis=-1, keepdims=True) + 1e-20)
    scale = float(attrs.get("scale", 1.0))
    return top_w if scale == 1.0 else top_w * scale


def _kept_groups(choice, groups, kept, top_k):
    """`choice` [tokens, experts] with -inf on every expert outside the
    token's `kept` best of `groups` groups of consecutive experts; a
    group scores the sum of its two largest entries (a largest that
    occurs twice counts twice), and of groups that score the same the
    lower index is the better: what a stable sort gives, without one.
    A group's largest entry, the largest of the others, and a group's
    rank counted over all pairs of groups.  `lax.top_k` is a stable sort
    of its whole last axis on the TPU, and [tokens, groups, width] is
    sorted with the 8 groups in a tile's 128 lanes: the two sorts were
    94 of the router's 132 us at 128 tokens x 512 experts (PERF.md
    section 6, PR 57)."""
    n, experts = choice.shape
    if experts % groups or not 0 < kept <= groups \
            or experts // groups < 2 or kept * (experts // groups) < top_k:
        raise ValueError(
            "moe_router: %d experts in %d groups of which %d are kept "
            "cannot give %d experts a token" % (experts, groups, kept,
                                                top_k))
    telemetry.on_moe_grouped_router_lowering(experts, groups, kept, top_k)
    with jax.named_scope("moe_groups"):
        width = experts // groups
        grouped = choice.reshape(n, groups, width)
        at = jnp.argmax(grouped, axis=-1)
        others = jnp.where(jnp.arange(width) == at[:, :, None], -jnp.inf,
                           grouped)
        score = jnp.max(grouped, axis=-1) + jnp.max(others, axis=-1)
        # ahead[t, g, h]: group h is chosen before group g
        mine, theirs = score[:, :, None], score[:, None, :]
        lower = jnp.arange(groups) < jnp.arange(groups)[:, None]
        ahead = (theirs > mine) | ((theirs == mine) & lower)
        keep = jnp.sum(ahead, axis=-1, dtype=jnp.int32) < kept
        return jnp.where(keep[:, :, None], grouped,
                         -jnp.inf).reshape(n, experts)


@register_op("moe_router", infer_shape=_router_infer_shape)
def moe_router(ctx, ins, attrs):
    """X [..., hidden], W [hidden, experts] -> Logits [tokens, experts],
    TopW and TopIdx [tokens, top_k] (the largest probabilities, largest
    first, as they are: no renormalising), LbLoss = experts * sum_e f_e
    P_e (f_e the share of the assignments that fell on expert e, a
    constant to the gradient; P_e the mean probability of e) and ZLoss
    = mean(logsumexp(logits)^2), each [1].  float32 throughout, the
    product at the highest precision: the TPU's default would round its
    operands to bfloat16.

    With `scoring` "sigmoid" the scores are sigmoid(logits), TopW the
    largest of them, and both losses 0.  `norm_topk` divides TopW by its
    sum over the chosen (+ 1e-20) and `scale` multiplies it, under
    either scoring.

    Under sigmoid scoring the *choice* can be steered apart from the
    weights, as DeepSeek-V3 routes (`noaux_tc`): Bias [experts] is added
    to the scores for the choice alone (s' = s + b; TopW still reads s),
    and with `n_group` > 1 the experts are `n_group` groups of
    consecutive ones, a group scores the sum of its two largest s', and
    an expert outside the `topk_group` best groups cannot be chosen
    whatever its score (`moe_groups`; of groups that score the same the
    lower index is the better, as of experts in the choice itself)."""
    x, w = ins["X"][0], ins["W"][0]
    k = int(attrs["top_k"])
    experts = w.shape[1]
    scoring = attrs.get("scoring", "softmax")
    bias = (ins.get("Bias") or [None])[0]
    groups = int(attrs.get("n_group", 0))
    logits = jnp.dot(x.reshape(-1, x.shape[-1]).astype(jnp.float32),
                     w.astype(jnp.float32),
                     precision=jax.lax.Precision.HIGHEST)
    if (bias is not None or groups > 1) and scoring != "sigmoid":
        raise ValueError("moe_router: a selection bias and groups steer "
                         "the sigmoid router's choice, scoring is %r"
                         % scoring)
    if scoring == "sigmoid":
        scores = jax.nn.sigmoid(logits)
        if bias is None and groups <= 1:
            top_w, top_idx = jax.lax.top_k(scores, k)
        else:
            choice = scores if bias is None \
                else scores + bias.astype(jnp.float32).reshape(1, experts)
            if groups > 1:
                choice = _kept_groups(choice, groups,
                                      int(attrs["topk_group"]), k)
            _, top_idx = jax.lax.top_k(choice, k)
            top_w = jnp.take_along_axis(scores, top_idx, axis=1)
        zero = jnp.zeros((1,), jnp.float32)
        return {"Logits": [logits], "TopW": [_chosen(top_w, attrs)],
                "TopIdx": [top_idx.astype(jnp.int32)],
                "LbLoss": [zero], "ZLoss": [zero]}
    if scoring != "softmax":
        raise ValueError("moe_router: scoring is softmax or sigmoid, got "
                         "%r" % scoring)
    lse = jax.nn.logsumexp(logits, axis=-1, keepdims=True)
    probs = jnp.exp(logits - lse)
    top_w, top_idx = jax.lax.top_k(probs, k)
    share = _rows_an_expert(top_idx, experts).astype(jnp.float32) \
        / top_idx.size
    lb = experts * jnp.sum(share * jnp.mean(probs, axis=0))
    z = jnp.mean(jnp.square(lse))
    return {"Logits": [logits], "TopW": [_chosen(top_w, attrs)],
            "TopIdx": [top_idx.astype(jnp.int32)],
            "LbLoss": [lb.reshape(1)], "ZLoss": [z.reshape(1)]}


def _experts_infer_shape(block, op_desc):
    x = block.var_recursive(op_desc.input("X")[0]).desc
    experts, hidden, width = block.var_recursive(
        op_desc.input("WGate")[0]).desc.shape
    rows = _tokens(x.shape) * block.var_recursive(
        op_desc.input("TopIdx")[0]).desc.shape[1]
    for slot, shape, dtype in (
            ("Out", x.shape, x.dtype), ("Xs", (rows, hidden), x.dtype),
            ("Gate", (rows, width), x.dtype), ("Up", (rows, width), x.dtype),
            ("RowSlot", (rows,), "int32"), ("TokenRow", (rows,), "int32"),
            ("Counts", (experts,), "int32")):
        _set_meta(block, op_desc.output(slot)[0], shape, dtype)


def _held_range(attrs, experts):
    """(first_expert, scored) of an expert op that holds `experts`."""
    return (int(attrs.get("first_expert", 0)),
            int(attrs.get("scored", 0)) or experts)


def _token_rows(rows, token_row, n, k):
    """[n, k, width]: each token's k rows of `rows`, in its own order."""
    return rows[token_row].reshape(n, k, rows.shape[-1])


def _by_key(keys, values):
    """`values` in the order that sorts `keys`, a permutation: with
    `keys` the inverse of a permutation p this is values[p], by a sort
    (19 us for 32768 on the v5e) in place of a gather of single
    elements (230-280 us)."""
    return jax.lax.sort((keys, values), num_keys=1)[1]


# The chunked row work of the range form (the module's docstring): only
# where an op orders at least so many rows, in chunks of so many rows of
# the order, a whole number of the grouped kernels' row tiles.  (On the
# v5e the op alone and the training cell read the same at 2048, 4096 and
# 8192 rows a chunk; a served prefill's loops at the two smaller ones
# left its decoding scan 1% slower: PERF.md section 5, PR 55.)
_CHUNK_MIN_ROWS = 32768
_CHUNK_ROWS = 8192
_ROW_TILE = 256
assert _CHUNK_ROWS % _ROW_TILE == 0


def _chunk_rows(n, k, held, scored):
    """The rows a trip takes of the loops that an op runs its row work
    in which orders `n * k` assignments and holds `held` of the `scored`
    experts, from the shapes alone; 0 where the op has the one plain
    body over every row: it holds every expert scored (no row to leave
    out), orders too few rows to pay a loop's fixed cost back, or no
    whole number of chunks."""
    rows = n * k
    if held >= scored or rows < _CHUNK_MIN_ROWS or rows % _CHUNK_ROWS:
        return 0
    return _CHUNK_ROWS


@contextlib.contextmanager
def _scopes(phase, chunked):
    """The op's scope `phase` and, inside it, `moe_compact` around row
    work that goes by chunks of the held rows."""
    with jax.named_scope(phase):
        if not chunked:
            yield
        else:
            with jax.named_scope("moe_compact"):
                yield


def _chunk_of(ordered, start, chunk):
    """`chunk` rows of an array of the order from row `start` on."""
    return jax.lax.dynamic_slice_in_dim(ordered, start, chunk)


def _over_held_chunks(work, counts, chunk, *whole):
    """The arrays `whole` of the order (a whole number of chunks) with
    their rows `start .. start + chunk` written over by `work(start,
    present, *those rows)` (a tuple, one [chunk, ...] an array), chunk
    by chunk from row 0 as far as row sum(counts), the first that is not
    held: sum(counts) // chunk + 1 trips, data on the device, each chunk
    written in place.  `present` [chunk, 1] says which of the chunk's
    rows are held: they come first in the order, so only the last trip's
    chunk has others (where the held rows fill their chunks it has no
    other, and what it costs buys this: the tile of row sum(counts),
    which `gmm_dw` visits for a held expert that got no row and
    multiplies by 0, holds what a trip wrote).  Rows past the last
    trip's chunk keep what `whole` had."""
    held = jnp.sum(counts)
    rows = whole[0].shape[0]

    def trip(i, carried):
        start = i * chunk
        present = (start + jnp.arange(chunk, dtype=jnp.int32) < held)[:, None]
        parts = work(start, present,
                     *(_chunk_of(a, start, chunk) for a in carried))
        return tuple(jax.lax.dynamic_update_slice_in_dim(a, part, start, 0)
                     for a, part in zip(carried, parts))

    return jax.lax.fori_loop(
        0, jnp.minimum(held // chunk + 1, rows // chunk), trip, whole)


def _row_work(phase, work, operands, counts, chunk, present=None, over=0):
    """`work(*operands, present)` row for row, under the op's scope
    `phase`: a tuple of arrays of the order from `operands`, arrays of
    the order.  With `chunk` 0 over every row at once, `present`
    [rows, 1] saying which are held (None: all).  Else over the chunks
    that hold a held row (`_over_held_chunks`): the first `over` results
    are written over the first `over` operands, which are given up (a
    grouped product's row operand, whose rows past the held groups' no
    product reads, needs no array of its own), and the others over
    arrays nothing has written (`grouped_matmul.unwritten`): what the
    rows past those chunks hold is nobody's to read."""
    from ..kernels import grouped_matmul

    with _scopes(phase, chunked=chunk):
        if not chunk:
            return work(*operands, present)
        rows = operands[0].shape[0]
        parts = jax.eval_shape(
            work, *(jax.ShapeDtypeStruct((chunk,) + a.shape[1:], a.dtype)
                    for a in operands),
            jax.ShapeDtypeStruct((chunk, 1), jnp.bool_))
        return _over_held_chunks(
            lambda start, present, *mine: work(
                *mine[:over],
                *(_chunk_of(a, start, chunk) for a in operands[over:]),
                present),
            counts, chunk, *operands[:over],
            *(grouped_matmul.unwritten((rows,) + p.shape[1:], p.dtype,
                                       after=operands[0])
              for p in parts[over:]))


def _held_token_sums(rows, token_row, counts, n, k, chunk, dtype,
                     weights=None, over=None):
    """[n, width] in `dtype`: each token's float32 sum of its held
    assignments' rows of `rows` [n * k, width], the first sum(counts)
    of the order, each times its entry of `weights` [n * k] (by slot)
    where given; the rows' sums are written over `over`, an array like
    `rows` in `dtype` that is given up, where there is one (else over an
    array nothing has written).  The held slots in slot order are in
    token order, so one sort of the n * k slots (held first) puts a
    token's rows side by side; then, `chunk` of them a trip, the rows
    are gathered, each adds the up to k - 1 after it that share its
    token, and a token reads the sum at its first row, which lies as far
    in as the tokens before it have held slots.  (A gather costs by the
    rows it fetches, 42 us a thousand of 2560 on the v5e whatever their
    order: each token fetching its k rows would fetch n * k.)"""
    from ..kernels import grouped_matmul

    f32 = jnp.float32
    slots = jnp.arange(n * k, dtype=jnp.int32)
    held = token_row < jnp.sum(counts)
    ordered = jax.lax.sort(
        (jnp.where(held, slots, n * k), token_row)
        + (() if weights is None else (weights,)), num_keys=1)
    # k - 1 entries past the end, the last rows' neighbours: a chunk's
    # shifted reads are then slices of one gathered array, in the rows'
    # own type, which one pass reads
    ordered = [jnp.pad(a, (0, k - 1), constant_values=fill)
               for a, fill in zip(ordered, (n * k, 0, 0))]

    def sums(start, *_):
        slot, row, *weight = (_chunk_of(a, start, chunk + k - 1)
                              for a in ordered)
        live = slot < n * k
        picked = rows[jnp.where(live, row, 0)]
        token = jnp.where(live, slot // k, -1)
        summed = 0.0
        for j in range(k):
            mine = picked[j:chunk + j].astype(f32)
            if weight:
                mine = mine * weight[0][j:chunk + j, None]
            same = live[j:chunk + j] & (token[j:chunk + j] == token[:chunk])
            summed = summed + jnp.where(same[:, None], mine, 0.0)
        return (summed.astype(dtype),)

    if over is None or (over.shape, over.dtype) != (rows.shape, dtype):
        over = grouped_matmul.unwritten(rows.shape, dtype, after=rows)
    (summed,) = _over_held_chunks(sums, counts, chunk, over)
    mine_per_token = jnp.sum(held.reshape(n, k), axis=1, dtype=jnp.int32)
    first = jnp.cumsum(mine_per_token) - mine_per_token
    return jnp.where((mine_per_token > 0)[:, None],
                     summed[jnp.minimum(first, n * k - 1)], 0)


def _held(some, present):
    """Rows of an ordered array with 0 in those of absent experts, which
    `present` [rows, 1] says are not there (None: every row is)."""
    if present is None:
        return some
    return jnp.where(present, some, jnp.zeros((), some.dtype))


def _gate(g, attrs):
    """(act(g), act'(g)) of the float32 pre-activation `g` under the
    op's `activation`: "silu" (the default, which an op need not
    carry) or "relu"."""
    activation = attrs.get("activation", "silu")
    limit = attrs.get("swiglu_limit")
    if limit:
        # the clamped form: silu(min(g, limit)), flat past the limit
        act, slope = _gate(jnp.minimum(g, limit),
                           dict(attrs, swiglu_limit=None))
        return act, jnp.where(g < limit, slope, 0.0)
    if activation == "silu":
        sig = jax.nn.sigmoid(g)
        act = g * sig
        return act, sig + act * (1.0 - sig)
    if activation == "relu":
        on = g > 0
        return jnp.where(on, g, 0.0), on.astype(g.dtype)
    raise ValueError("moe_experts: activation is silu or relu, got %r"
                     % activation)


def _up(u, attrs):
    """(the up projection as the product reads it, its slope): `u`
    itself, or under `swiglu_limit` clip(u, -limit, limit)."""
    limit = attrs.get("swiglu_limit")
    if not limit:
        return u, 1.0
    return jnp.clip(u, -limit, limit), (jnp.abs(u) < limit).astype(u.dtype)


@register_op("moe_experts", nondiff_inputs=("TopIdx",),
             infer_shape=_experts_infer_shape)
def moe_experts(ctx, ins, attrs):
    """X [..., hidden], TopW / TopIdx [tokens, top_k], WGate and WUp
    [experts, hidden, width], WDown [experts, width, hidden] -> Out, X's
    shape: sum_j TopW[n, j] * down_e(act(gate_e(x_n)) * up_e(x_n)) with
    e = TopIdx[n, j] and act the op's `activation` (SiLU; "relu": ReLU;
    under `swiglu_limit` L the clamped form act(min(gate, L)) * clip(up,
    -L, L));
    and what the gradient reads (the module's docstring).  Where the router scores `scored` experts and the op
    holds fewer (`experts` of them from `first_expert` on), the sum is
    over the held e alone."""
    from ..kernels.grouped_matmul import gmm

    x, top_w, top_idx = ins["X"][0], ins["TopW"][0], ins["TopIdx"][0]
    w_gate, w_up, w_down = (ins[s][0] for s in ("WGate", "WUp", "WDown"))
    n, k = top_idx.shape
    experts = w_gate.shape[0]
    telemetry.on_moe_lowering(experts, k)
    first, scored = _held_range(attrs, experts)
    ranged = (first, scored) != (0, experts)

    with jax.named_scope("moe_route"):
        flat = top_idx.reshape(-1).astype(jnp.int32)
        if ranged:
            telemetry.on_moe_share_lowering(scored, experts, k)
            with jax.named_scope("moe_hold"):
                # an absent expert's assignments sort behind every held
                # one's and are counted for no group
                flat = flat - first
                held = (flat >= 0) & (flat < experts)
                flat = jnp.where(held, flat, experts)
        slots = jnp.arange(n * k, dtype=jnp.int32)
        # row r of the order holds slot row_slot[r] = token * k + j, and
        # slot s lies in row token_row[s]
        _, row_slot = jax.lax.sort((flat, slots), num_keys=1, is_stable=True)
        token_row = _by_key(row_slot, slots)
        counts = _rows_an_expert(flat, experts)
        x2 = x.reshape(n, x.shape[-1])
        (xs,) = mxu_operands(x2[row_slot // k])
    with jax.named_scope("moe_experts"):
        wg, wu, wd = mxu_operands(w_gate, w_up, w_down)
        gate = gmm(xs, wg, counts)
        up = gmm(xs, wu, counts)

    rows, chunk = n * k, _chunk_rows(n, k, experts, scored)
    f32 = jnp.float32
    if chunk:
        telemetry.on_moe_share_compact_lowering(rows, chunk)
    (h,) = _row_work(
        "moe_experts",
        lambda g, u, _: ((_gate(g.astype(f32), attrs)[0]
                          * _up(u.astype(f32), attrs)[0]).astype(xs.dtype),),
        (gate, up), counts, chunk)
    with jax.named_scope("moe_experts"):
        y = gmm(h, wd, counts)
    with _scopes("moe_combine", chunked=chunk):
        if chunk:
            out = _held_token_sums(
                y, token_row, counts, n, k, chunk,
                amp_result(jnp.zeros((), f32), x.dtype).dtype,
                top_w.astype(f32).reshape(-1))
        else:
            mine = _token_rows(y, token_row, n, k).astype(f32)
            if ranged:
                # no product wrote an absent assignment's row
                mine = jnp.where(held.reshape(n, k, 1), mine, 0.0)
            out = jnp.sum(mine * top_w.astype(f32)[..., None], axis=1)
    out = amp_result(out, x.dtype).reshape(x.shape)
    return {"Out": [out], "Xs": [xs], "Gate": [gate], "Up": [up],
            "RowSlot": [row_slot], "TokenRow": [token_row],
            "Counts": [counts]}


@register_grad_kernel("moe_experts")
def moe_experts_grad(ctx, ins, attrs):
    """X@GRAD, TopW@GRAD and the three weights' gradients from what the
    forward op kept: six grouped products (two `gmm_dx` over the
    gate/up pair, one over down, three `gmm_dw`), the weights'
    gradients added up and returned in float32.  Where the op holds a
    range of the experts scored, the rows past the held groups' belong
    to absent experts: no product wrote them, forward or here, so what
    they hold is set to 0 wherever it would reach a sum."""
    from ..kernels.grouped_matmul import gmm_dw, gmm_dx

    x, top_w = ins["X"][0], ins["TopW"][0]
    w_gate, w_up, w_down = (ins[s][0] for s in ("WGate", "WUp", "WDown"))
    experts = w_gate.shape[0]
    first, scored = _held_range(attrs, experts)
    ranged = (first, scored) != (0, experts)
    xs, gate, up, row_slot, token_row, counts = (
        ins["O@" + s][0] for s in ("Xs", "Gate", "Up", "RowSlot",
                                   "TokenRow", "Counts"))
    n, k = top_w.shape
    d_out = ins["OG@Out"][0].reshape(n, x.shape[-1])
    f32 = jnp.float32

    rows, chunk = n * k, _chunk_rows(n, k, experts, scored)
    present = None
    if ranged:
        telemetry.on_moe_share_bwd_lowering(scored, experts, k)
        if not chunk:
            with jax.named_scope("moe_route"), jax.named_scope("moe_hold"):
                # the order puts the held groups' rows first
                present = (jnp.arange(rows, dtype=jnp.int32)
                           < jnp.sum(counts))[:, None]

    def around_activation(g, u, dh_raw, w, present):
        """From rows of Gate, Up, the down product's gradient to its
        rows and the routing weights: the gate's, the up's and the down
        product's row operands of the five products that follow (in the
        places of Gate, Up and that gradient), and the rows' part of
        the routing weights' gradient."""
        g, u, dh_raw = (_held(a, present).astype(f32)
                        for a in (g, u, dh_raw))
        act, d_act = _gate(g, attrs)
        u, d_u = _up(u, attrs)
        h = act * u
        # dh_raw is d<y_row, dOut> / dh, before the routing weight
        d_w_rows = jnp.sum(dh_raw * h, axis=-1)
        dh = dh_raw * w[:, None]
        d_gate = (dh * u * d_act).astype(xs.dtype)
        d_up = (dh * act * d_u).astype(xs.dtype)
        hw = (h * w[:, None]).astype(xs.dtype)
        return d_gate, d_up, hw, d_w_rows

    def row_work(phase, work, *operands, over=0):
        return _row_work(phase, work, operands, counts, chunk, present, over)

    with jax.named_scope("moe_combine"):
        d_tokens = d_out.astype(xs.dtype)
        # every row's routing weight
        w_rows = _by_key(token_row, top_w.astype(f32).reshape(-1))
    # dOut of every row's token
    (d_rows,) = row_work(
        "moe_combine", lambda slot, _: (d_tokens[slot // k],), row_slot)
    with jax.named_scope("moe_experts"):
        wg, wu, wd = mxu_operands(w_gate, w_up, w_down)
        dh_raw = gmm_dx(d_rows, wd, counts)
    d_gate, d_up, hw, d_w_rows = row_work(
        "moe_experts", around_activation, gate, up, dh_raw, w_rows, over=3)
    with jax.named_scope("moe_experts"):
        d_w_down = gmm_dw(hw, d_rows, counts)
        d_w_gate = gmm_dw(xs, d_gate, counts)
        d_w_up = gmm_dw(xs, d_up, counts)
        by_gate = gmm_dx(d_gate, wg, counts)
        by_up = gmm_dx(d_up, wu, counts)
    (d_xs,) = row_work(
        "moe_experts",
        lambda a, b, present: (
            _held((a.astype(f32) + b.astype(f32)).astype(xs.dtype),
                  present),),
        by_gate, by_up, over=1)
    with _scopes("moe_route", chunked=chunk):
        if chunk:
            # over the up product's input gradient, which nothing but
            # the sum above read
            d_x = _held_token_sums(d_xs, token_row, counts, n, k, chunk,
                                   d_out.dtype, over=by_up)
        else:
            d_x = jnp.sum(_token_rows(d_xs, token_row, n, k).astype(f32),
                          axis=1)
    with jax.named_scope("moe_route"):
        if chunk:
            # no trip wrote the rows past the last one's chunk: an absent
            # assignment's routing weight has a gradient of 0
            d_w_rows = jnp.where(
                jnp.arange(rows, dtype=jnp.int32) < jnp.sum(counts),
                d_w_rows, 0.0)
        d_top_w = _by_key(row_slot, d_w_rows).reshape(n, k)
    return {"X@GRAD": [d_x.astype(d_out.dtype).reshape(x.shape)],
            "TopW@GRAD": [d_top_w.astype(top_w.dtype)],
            "WGate@GRAD": [d_w_gate.astype(w_gate.dtype)],
            "WUp@GRAD": [d_w_up.astype(w_up.dtype)],
            "WDown@GRAD": [d_w_down.astype(w_down.dtype)]}
