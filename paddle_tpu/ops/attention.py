"""Attention ops: the pallas flash kernel as a registered framework op.

The reference's attention is composed ops that materialize the [T,T]
probability matrix (reference: python/paddle/v2/fluid/nets.py:338
scaled_dot_product_attention); registering the fused kernel as a
first-class op exceeds that surface: programs built with
`fluid.layers.flash_attention` get the pallas online-softmax kernel
(kernels/flash_attention.py) on TPU, interpret mode on CPU.  The op
hands Q, K and V to the kernels as they arrive, [batch, seq,
heads * dim], and `Out` is what the kernel wrote: the kernels'
BlockSpecs pick the heads (two 64-wide ones a grid step), so no head
is transposed around them.  The op keeps the kernel's row statistics
as a second output, `Lse`, and its gradient is an explicit kernel that
hands them to the backward kernels, in the same layout: the generic
gradient (jax.vjp of the whole op) has to run the forward kernel again
to get them back, which no compiler pass undoes for a custom call.

When the op's `sequence_parallel_axis` attr names an axis of the
ambient device mesh (the mesh `ParallelTrainer` compiles under), the
kernel runs ring attention instead: q/k/v stay sequence-sharded and
K/V blocks rotate over ICI neighbors (parallel/ring.py), so fluid-built
programs scale to long context without leaving the Program stack.
That branch splits the heads into [batch, heads, seq, dim], which the
ring's shards and ulysses' all-to-all over heads need.
The gradient of that branch, and of a program built before the op had
`Lse`, is the generic one.

With the attr `window` W > 0 (and `causal`) a query attends its last W
keys alone: the kernels bound their chunk loops and index maps to them
(kernels/flash_attention.py), forward and backward, under the scope
`attn_window` (`attn_full` with no window, the names `cached_attention`
gives its two kinds of cache); the sequence-parallel branches take no
window.
"""

import math

import jax
import jax.numpy as jnp
import numpy as np

from ..obs import telemetry
from .registry import (register_op, register_grad_kernel, run_generic_grad,
                       same_meta_infer_shape)


def _ambient_mesh():
    """The (abstract) mesh of the enclosing `with jax.set_mesh(mesh):`
    scope, empty if not inside one — how a program-level op discovers
    the sp topology without threading a mesh argument through every
    layer."""
    return jax.sharding.get_abstract_mesh()


def _window_scope(attrs):
    """(window, scope): the op's window, and the name its kernels lie
    under in a trace."""
    window = int(attrs.get("window", 0))
    return window, "attn_window" if window else "attn_full"


def _sequence_parallel(attrs):
    """The ambient mesh if the op's `sequence_parallel_axis` names an
    axis of it larger than 1, else None: what sends the op, and its
    gradient after it, down the ring or ulysses path."""
    sp_axis = attrs.get("sequence_parallel_axis", "")
    mesh = _ambient_mesh()
    if sp_axis and not mesh.empty and mesh.shape.get(sp_axis, 1) > 1:
        return mesh
    return None


@register_op("flash_attention")
def flash_attention_op(ctx, ins, attrs):
    """Q,K,V: [batch, seq, dim] dense, dim = heads * head size; Out:
    [batch, seq_q, dim]; Lse: float32 [batch, heads, seq_q], the
    log-sum-exp of each row of scores, which the gradient reads (zeros
    on the sequence-parallel branches, whose gradient reads none)."""
    from ..kernels.flash_attention import (flash_attention_with_lse,
                                           merge_heads, split_heads)
    from ..parallel.ring import (ring_attention, ulysses_attention,
                                 sp_shard_map)

    q, k, v = ins["Q"][0], ins["K"][0], ins["V"][0]
    num_heads = int(attrs.get("num_heads", 1))
    causal = bool(attrs.get("causal", False))
    sm_scale = float(attrs.get("sm_scale", 0.0)) or None
    sp_axis = attrs.get("sequence_parallel_axis", "")
    sp_mode = attrs.get("sequence_parallel_mode", "ring")

    for name, t in (("Q", q), ("K", k), ("V", v)):
        if t.ndim != 3:
            raise ValueError("flash_attention %s must be 3-D "
                             "[batch, seq, dim], got %s" % (name, t.shape))
        if t.shape[-1] % num_heads:
            raise ValueError("hidden size %d must divide num_heads %d"
                             % (t.shape[-1], num_heads))

    window, scope = _window_scope(attrs)
    mesh = _sequence_parallel(attrs)
    if mesh is not None and window:
        raise ValueError("flash_attention: the sequence-parallel paths "
                         "take no window, got %d" % window)
    if mesh is not None:
        qh, kh, vh = (split_heads(x, num_heads) for x in (q, k, v))
        if sp_mode == "ring":
            sp_fn = lambda q, k, v: ring_attention(  # noqa: E731
                q, k, v, sp_axis, sm_scale, causal)
        elif sp_mode == "ulysses":
            # all-to-all trades the sequence shard for a head shard:
            # local flash attention over full sequences for H/sp heads
            sp_fn = lambda q, k, v: ulysses_attention(  # noqa: E731
                q, k, v, sp_axis, sm_scale, causal)
        else:
            raise ValueError(
                "sequence_parallel_mode must be ring or ulysses, got %r"
                % sp_mode)
        out = merge_heads(
            sp_shard_map(sp_fn, mesh, axis_name=sp_axis)(qh, kh, vh))
        lse = jnp.zeros(qh.shape[:3], jnp.float32)
    else:
        # 0: the kernel chooses its blocks from the shapes
        block = int(attrs.get("block_size", 0)) or None
        with jax.named_scope(scope):
            out, lse = flash_attention_with_lse(
                q, k, v, sm_scale, causal, block_q=block, block_k=block,
                num_heads=num_heads, window=window)
    return {"Out": [out.astype(q.dtype)], "Lse": [lse]}


@register_grad_kernel("flash_attention")
def flash_attention_grad(ctx, ins, attrs):
    """Q@GRAD, K@GRAD, V@GRAD from the backward kernels on what the
    forward op saved: `O@Lse`, and `O@Out` for the row sums of
    dOut * Out; operands and gradients stay [batch, seq, dim], as the
    forward's.  Where the forward took the sequence-parallel path, or
    the op desc carries no `O@Lse` (a program from before the op had
    it), the generic gradient differentiates the op as a whole and runs
    its forward again."""
    from ..kernels.flash_attention import BWD_SCOPE, _bwd, row_sums

    lse = (ins.get("O@Lse") or [None])[0]
    if lse is None or _sequence_parallel(attrs) is not None:
        telemetry.on_flash_attention_grad_lowering("recomputed")
        return run_generic_grad(ctx, "flash_attention", ins, attrs)
    telemetry.on_flash_attention_grad_lowering("saved")

    q, k, v, o = (ins[slot][0] for slot in ("Q", "K", "V", "O@Out"))
    do = ins["OG@Out"][0].astype(q.dtype)
    num_heads = int(attrs.get("num_heads", 1))
    block = int(attrs.get("block_size", 0)) or None
    # the scope holds what it holds under the kernel's own VJP: the row
    # sums and the kernels
    window, scope = _window_scope(attrs)
    with jax.named_scope(BWD_SCOPE), jax.named_scope(scope):
        grads = _bwd(q, k, v, do, lse, row_sums(do, o, num_heads),
                     float(attrs.get("sm_scale", 0.0)) or None,
                     bool(attrs.get("causal", False)), block, block,
                     num_heads=num_heads, window=window)
    return {slot + "@GRAD": [g]
            for slot, g in zip(("Q", "K", "V"), grads)}


def yarn_inv_freq(dim, theta, factor, original_positions, beta_fast,
                  beta_slow):
    """YaRN's blended inverse frequencies (Peng et al. 2023,
    arXiv:2309.00071, as the DeepSeek-V3 family's released inference
    code makes them) of a rotary width `dim`, as `dim // 2` Python
    floats: with f_i = theta^(-2i / dim) and corr(n) = dim ln(original /
    (2 pi n)) / (2 ln theta) the pair index that turns n times over the
    original context, lo = max(floor(corr(beta_fast)), 0), hi =
    min(ceil(corr(beta_slow)), dim - 1), ramp_i = clip((i - lo) / (hi -
    lo), 0, 1):

        f'_i = f_i / factor * ramp_i + f_i * (1 - ramp_i)

    (pairs that turn often keep their frequency, those that never
    complete a turn over the original context are slowed by `factor`)."""
    def corr(turns):
        return dim * math.log(original_positions / (turns * 2 * math.pi)) \
            / (2 * math.log(theta))

    lo = max(math.floor(corr(beta_fast)), 0)
    hi = min(math.ceil(corr(beta_slow)), dim - 1)
    span = (hi - lo) or 0.001
    out = []
    for i in range(dim // 2):
        f = theta ** (-2.0 * i / dim)
        ramp = min(max((i - lo) / span, 0.0), 1.0)
        out.append(f / factor * ramp + f * (1.0 - ramp))
    return out


def yarn_mscale(factor, mscale=1.0):
    """What YaRN multiplies attention's logits' scale by, squared by the
    caller (once for the query, once for the key): 0.1 mscale ln(factor)
    + 1, and 1 where nothing is stretched."""
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def _rope_full_width(x, cos, sin, num_heads, rotary):
    """`rope` on x [batch, seq, heads * head] where it lies, with cos
    and sin [batch, seq, 1, rotary / 2]: x * C + partner(x) * S over
    rows of `lanes` values, whole heads of them (128 where a head
    divides that), C and S the cosines and signed sines over a head's
    width (1 and 0 past `rotary`), repeated a head of the row and the
    same for every row of a token; a value's partner is the one half a
    rotary width to its right (first half) or left (second), which a
    product with a 0 / 1 matrix picks out exactly: the lanes are turned
    on the MXU."""
    b, t, d = x.shape
    head, half = d // num_heads, rotary // 2
    lanes = head * max(1, 128 // head)
    if d % lanes:
        lanes = head
    j = np.arange(lanes)
    turned = j % head < rotary
    pick = np.zeros((lanes, lanes), np.float32)
    pick[np.where(j % head < half, j + half, j - half)[turned],
         j[turned]] = 1
    rows = x.reshape(b * t, d // lanes, lanes)
    partner = jnp.einsum(
        "nrk,kl->nrl", rows, jnp.asarray(pick, x.dtype),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32)
    rest = jnp.ones((b, t, 1, head - rotary), jnp.float32)
    by_cos = jnp.tile(jnp.concatenate([cos, cos, rest], axis=-1),
                      (1, 1, 1, lanes // head)).reshape(b * t, 1, lanes)
    by_sin = jnp.tile(jnp.concatenate([-sin, sin, 0 * rest], axis=-1),
                      (1, 1, 1, lanes // head)).reshape(b * t, 1, lanes)
    return (rows.astype(jnp.float32) * by_cos + partner * by_sin) \
        .astype(x.dtype).reshape(b, t, d)


@register_op("rope", nondiff_inputs=("Positions",),
             infer_shape=same_meta_infer_shape("X", "Out"))
def rope(ctx, ins, attrs):
    """Rotary position embedding (Su et al. 2021) in the rotate-half
    form, on each head of X [batch, seq, heads * head_dim] at Positions
    [batch, seq]: the pair (x_i, x_{i + head_dim/2}) is turned by the
    angle position * theta^(-2i / head_dim).  Angles and rotation in
    float32, the result in X's type.

    `inv_freq` (a list of floats, one a pair) gives the angles' rates in
    place of theta's powers (`yarn_inv_freq` makes YaRN's); `rotary_dim`
    turns only the first so many values of every head, paired (x_i,
    x_{i + rotary_dim/2}), and hands the rest on as they are.

    `full_width` (an attr a builder sets for a step that takes a block
    of positions) turns a block of seq > 1 positions where it lies,
    [batch, seq, heads * head_dim] throughout: a value's partner is the
    one half a rotary width to its left or right, and cosines and signed
    sines are laid out over a head's width and repeated a head.  The
    same products and sums as below, so the same numbers; what differs
    is that no [.., 2, head_dim / 2] view is made, whose 32 values a row
    fill a quarter of the TPU's 128 lanes: at 256 x 16 tokens of 128
    heads of 64 the views cost 5.9 ms an op on the v5e where the block
    of values is 67 MB (PERF.md section 6, PR 53).  One position is
    turned as it was.

    `sections` (a list of three pair counts that add up to the rotated
    pairs: Qwen2-VL's multimodal rotary positions, arXiv:2409.12191,
    `mrope_section`) gives a token three positions, Positions [3, batch,
    seq] (temporal, height, width): pair i is turned by the component
    its section names, the first `sections[0]` pairs by the temporal
    one, the next `sections[1]` by the height, the rest by the width, at
    the rate theta^(-2i / head_dim) of its own index i.  A text token
    carries one position three times and is turned as without
    `sections`."""
    x = ins["X"][0]
    pos = ins["Positions"][0]
    num_heads = int(attrs["num_heads"])
    theta = float(attrs.get("theta", 10000.0))
    b, t, d = x.shape
    if d % (2 * num_heads):
        raise ValueError("rope: hidden size %d is not num_heads %d times "
                         "an even head size" % (d, num_heads))
    head = d // num_heads
    rotary = int(attrs.get("rotary_dim", 0)) or head
    if rotary % 2 or rotary > head:
        raise ValueError("rope: rotary_dim %d is not an even part of a "
                         "head of %d" % (rotary, head))
    half = rotary // 2
    if attrs.get("inv_freq"):
        if len(attrs["inv_freq"]) != half:
            raise ValueError("rope: %d inverse frequencies for %d pairs"
                             % (len(attrs["inv_freq"]), half))
        inv_freq = jnp.asarray(attrs["inv_freq"], jnp.float32)
    else:
        inv_freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    sections = [int(n) for n in attrs.get("sections") or ()]
    if sections:
        if len(sections) != 3 or sum(sections) != half \
                or pos.shape != (3, b, t):
            raise ValueError(
                "rope: sections %s over %d pairs with Positions %s: three "
                "counts that add up to the pairs, and Positions [3, %d, %d]"
                % (sections, half, pos.shape, b, t))
        telemetry.on_sectioned_rope_lowering(num_heads, sections, t)
        # [b, t, pairs]: pair i reads the component of its section
        pos = jnp.moveaxis(pos, 0, -1).astype(jnp.float32)[
            ..., np.repeat(np.arange(3), sections)]
        angles = pos.reshape(b, t, 1, half) * inv_freq
    else:
        angles = pos.reshape(b, t, 1, 1).astype(jnp.float32) * inv_freq
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    if attrs.get("full_width") and t > 1:
        return {"Out": [_rope_full_width(x, cos, sin, num_heads, rotary)]}
    if rotary == head:
        xs = x.astype(jnp.float32).reshape(b, t, num_heads, 2, half)
        x1, x2 = xs[..., 0, :], xs[..., 1, :]
        out = jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                        axis=-2)
        return {"Out": [out.reshape(b, t, d).astype(x.dtype)]}
    xs = x.astype(jnp.float32).reshape(b, t, num_heads, head)
    x1, x2 = xs[..., :half], xs[..., half:rotary]
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin,
                           xs[..., rotary:]], axis=-1)
    return {"Out": [out.reshape(b, t, d).astype(x.dtype)]}


def _cached_attention_infer_shape(block, op_desc):
    """`Out` is `Q`'s, each cache comes out as it went in: stated, not
    traced, so that a block axis the Program leaves open (-1) stays
    open and is never written into the cache's extent."""
    for src, dst in (("Q", "Out"), ("KCache", "KCacheOut"),
                     ("VCache", "VCacheOut")):
        if dst in op_desc.outputs:  # the read-only form gives no cache
            same_meta_infer_shape(src, dst)(block, op_desc)


@register_op("cached_attention", stop_gradient_op=True,
             infer_shape=_cached_attention_infer_shape)
def cached_attention_op(ctx, ins, attrs):
    """Autoregressive attention through a KV cache, over a block of
    T >= 1 consecutive positions of every row: a decode step is T = 1
    (O(1) work per token instead of re-attending the whole window), a
    prompt's prefill takes many positions at once.

    Q: [batch, T, heads * head_dim]; KNew/VNew: [batch, T, kv_heads *
    head_dim] (the block's projections); KCache/VCache: [batch,
    kv_heads, slots, head_dim]; Position: int [1] or [batch] (lockstep
    rows), the position of the block's first entry.
    Outputs the attended context [batch, T, heads * head_dim] and the
    updated caches — wire them as ProgramDecoder state pairs.
    Generation never needs gradients (matching the reference's host-side
    generation loop), so the op stops them.

    `num_kv_heads` (0: as many as `num_heads`) fewer than `num_heads` is
    grouped-query attention: query head j reads key/value head j //
    (num_heads / num_kv_heads), by index; no key or value is repeated in
    memory.

    `window` 0: the cache holds the whole extent, slots Position ..
    Position + T - 1 are written, and query i of the block attends
    slots 0 .. Position + i.  `window` > 0: the cache is a ring of
    `window` slots, position p lives in slot p mod window, and a query
    sees itself and the window - 1 positions before it, whatever the
    sequence's length (a softmax does not care in which order the ring
    holds them).  A step (T = 1) writes its slot and attends the
    min(Position + 1, window) slots that hold something.  A block (T >
    1, any T against any window) attends before it writes: query i
    reads the ring as it stood before the block, the slots whose
    position is at least Position + i - window + 1 (and that were ever
    written), beside the block's own keys j with i - window < j <= i,
    in the type the ring would have held them in, under one mask and one
    softmax; then the block's last min(T, window) entries go to slots
    (Position + j) mod window: the ring holds, slot for slot, what T
    single steps leave there.

    Scopes: `kv_write` the caches' update, `attn_window` or `attn_full`
    (`attn_sparse` over a chosen set, below) everything between the
    caches and Out.  Over heads a multiple of 128
    wide (128; 256, two lane blocks a head) and a multiple of 128 slots
    the live slots alone are walked
    (kernels/gqa_decode.py: operands in Q's type, float32 sums and
    softmax): a step over either kind of cache, and a block over a
    whole extent where its group's T queries a key/value head fit the
    kernel (its float32 scores on the plain path would be [batch, heads,
    T, slots]).  Over 64-wide heads, one query a key/value head and
    caches in Q's type, a step (T = 1) likewise, and the kernel's file
    writes the slot too: it takes the caches slots-minor, where no lane
    of a 64-wide head is padding.  Every other shape takes the plain
    path, scores over every slot under a mask (a block through a ring:
    over `window` + T keys).

    No operand is narrower at T > 1 than at T = 1 on the plain path:
    both products read their operands as float32 at the highest
    precision (on the TPU the default rounds a float32 operand to
    bfloat16, the probabilities among them; a one-row product never
    reaches the MXU and is exact anyway), the mask and the softmax are
    float32.

    With Selected int32 [batch, top_k] and Live int32 [batch]
    (`mla_index_select`'s two) a step attends a chosen set and not every
    live slot (whole-extent caches, `window` 0; a ring or a Selected
    without Live raises): the step's slot is written, the slots Selected names are
    gathered from both caches, one set for every key/value head
    (`kv_gather`; an entry is clipped into the extent), and the group's
    queries attend the first Live of a row's top_k entries
    (`attn_sparse`).  Over 128-wide heads whose count fills the 32-bit
    words of a sublane row (even, or one) and a top_k that a chunk of
    128 to 2048 entries tiles, whole slots are gathered, a slot's heads
    side by side ([batch, top_k, kv_heads, head_dim]: where the caches
    lie heads-minor, as a decoder's scan carries them, that is one fetch
    a slot and what the gather writes anyway), and
    `kernels/gqa_decode.py gqa_decode_chosen` reads the copies as they
    lie: no pass turns or fills them in between.  Every other set takes
    two [batch, kv_heads, top_k, head_dim] copies and the plain path
    under the mask entry < Live.  A chosen set is a set: the softmax
    does not care for its order.  A chosen set is one position's: a
    block of T > 1 positions comes with Selected [batch, T, top_k] and
    Live [batch, T], a set a position (`mla_index_select`'s for T
    queries).  The block's T entries are written first, slots Position
    .. Position + T - 1 of both caches, so that query t may choose and
    read the slots of the block's positions up to its own, as T single
    steps would; then a tile of P positions at a time
    (`_CHOSEN_TILE_BYTES`: a position's two copies, beside its scores on
    the plain path, within half the chip's fast memory) the
    tile's [batch, P * top_k] slots are gathered from each cache in one
    gather of the kind a step makes, and attended a position a row:
    `gqa_decode_chosen` over batch * P rows, each under its own Live[b,
    t] (a session that has not filled its top_k yet has one live entry
    more a position), or the plain products under entry < Live[b, t].
    Nothing is shared between the sets: the gathers of a block are those
    of its T steps, and the caches come out bit for bit as T steps leave
    them.  `prefill_block` (an attr) is the most positions a block of
    this op was sized for (what a step's builder states for
    `fluid.ProgramDecoder` to prefill by): a longer block is refused.

    Without KNew and VNew the op **reads a cache it does not write**
    (whole-extent caches, no chosen set; anything else raises): query i
    attends slots 0 .. Position + i of KCache / VCache as they are
    handed in, nothing is written and no cache comes out.  A layer that
    attends another layer's keys and values is wired to that layer's
    KCacheOut / VCacheOut, so that write-then-read is the Program's data
    flow and the slot of the step's own position is among those read.
    Its scope is `attn_cross`, and it has no `kv_write`.  The attr
    `reader` says which of the cache's readers the op is (its builder
    numbers them from 1) and `shared_readers`, on the op that writes
    such a cache, how many they are: both are for the counters alone
    (obs/telemetry.py `on_decoder_positions`).

    `diffusion_block` B > 0 (whole-extent caches it writes, no chosen
    set; T a multiple of B) is the **block-causal** mask of generation
    by diffusion over blocks: the T positions are T / B blocks of B
    counted from Position (which such a decoder keeps a multiple of B),
    and query i attends slots 0 .. Position + B (i // B + 1) - 1, every
    slot up to the end of its own block, the later positions of its
    block among them.  A denoising pass is T = B (all of the block, both
    directions), a prompt's prefill many whole blocks.  The slots are
    written as ever, before they are read: a pass that is not to be kept
    is overwritten by the next, and nothing reads past the block.  Its
    scope is `attn_block_causal`, the walk's kernel says `_b<B>`, and
    with the attr 0 the op is, instruction for instruction, what it was.
    """
    q = ins["Q"][0]
    readonly = not ins.get("KNew")
    k_new, v_new = (None, None) if readonly \
        else (ins["KNew"][0], ins["VNew"][0])
    k_cache, v_cache = ins["KCache"][0], ins["VCache"][0]
    selected = (ins.get("Selected") or [None])[0]
    # Position may be [1] or per-row [batch] (rows advance in lockstep;
    # a per-row vector is what beam expansion produces)
    pos = jnp.reshape(ins["Position"][0], (-1,))[0].astype(jnp.int32)
    num_heads = int(attrs.get("num_heads", 1))
    kv_heads = int(attrs.get("num_kv_heads", 0)) or num_heads
    window = int(attrs.get("window", 0))
    sm_scale = float(attrs.get("sm_scale", 0.0)) or None
    diffusion = int(attrs.get("diffusion_block", 0))
    rows, block, width = q.shape
    extent = k_cache.shape[2]
    if diffusion and (window or readonly or selected is not None
                      or block % diffusion):
        raise ValueError(
            "cached_attention: diffusion_block %d over a block of %d "
            "positions (window %d%s%s): the block-causal mask is whole "
            "blocks' over whole-extent caches the op writes"
            % (diffusion, block, window, ", read-only" if readonly else "",
               ", Selected" if selected is not None else ""))
    if num_heads % kv_heads or k_cache.shape[1] != kv_heads \
            or (window and window != extent):
        raise ValueError(
            "cached_attention: %d query heads over %d key/value heads, "
            "caches %s, window %d: the heads do not group, or the cache "
            "is not those heads' or not the window's ring"
            % (num_heads, kv_heads, k_cache.shape, window))
    if selected is not None and (
            window or not ins.get("Live") or tuple(selected.shape[:-1])
            != ((rows,) if block == 1 else (rows, block))):
        raise ValueError(
            "cached_attention: Selected %s with window %d over a block of "
            "%d positions of %d rows%s: a chosen set is one position's over "
            "whole-extent caches, [batch, top_k] for a step and [batch, T, "
            "top_k] for a block, and comes with Live"
            % (selected.shape, window, block, rows,
               "" if ins.get("Live") else ", without Live"))
    if block > int(attrs.get("prefill_block", 0) or block):
        raise ValueError(
            "cached_attention: a block of %d positions, and the op was "
            "sized for %d (`prefill_block`)"
            % (block, attrs["prefill_block"]))
    if readonly and (window or selected is not None or ins.get("VNew")):
        raise ValueError(
            "cached_attention: without KNew and VNew the op reads "
            "whole-extent caches another op wrote (window %d%s)"
            % (window, ", Selected" if selected is not None else ""))
    group = num_heads // kv_heads
    kind = "cross" if readonly else "window" if window \
        else "sparse" if selected is not None \
        else "block_causal" if diffusion else "full"
    ring_block = window > 0 and block > 1
    # the slots a query's products run over: a chosen set's, else the
    # cache's own
    attended = extent if selected is None else selected.shape[-1]

    # [B, T, H * Dh] -> [B, H, T, Dh]: kernels/flash_attention.py has
    # the same two lines behind an import of Pallas, which a decoder
    # would pay at its first trace for a reshape
    qh = q.reshape(rows, block, num_heads, -1).transpose(0, 2, 1, 3)
    kh, vh = (None, None) if readonly else (
        x.reshape(rows, block, kv_heads, -1).transpose(0, 2, 1, 3)
        for x in (k_new, v_new))
    head_dim = qh.shape[-1]
    if sm_scale is None:
        sm_scale = head_dim ** -0.5
    # the walk of the live slots where what the op sees of its inputs
    # fits it (Pallas is imported for the kernel's head widths alone).
    # 64-wide heads: a step over whole-extent caches in Q's type, which
    # the kernel reads as they lie; a cache in another type keeps the
    # plain path, where reading it up would write a copy of it a layer a
    # step
    # (the kernel's widths: 64, and the multiples of the 128 lanes: 128,
    # and 256 as two lane blocks a head)
    block_k = 0
    if selected is not None:
        # a chosen set: the kernel over the slots as their gather leaves
        # them, where it takes the heads' width and count
        from ..kernels import gqa_decode
        block_k = gqa_decode.choose_chunk(
            attended, kv_heads, group, q.dtype.itemsize, head_dim)
    elif (head_dim == 64 or head_dim % 128 == 0) and not ring_block and (
            head_dim != 64 or not (window or readonly)
            and k_cache.dtype == v_cache.dtype == q.dtype):
        from ..kernels import gqa_decode
        block_k = gqa_decode.choose_block(attended, group * block,
                                          q.dtype.itemsize, head_dim)
    writes = block_k and head_dim == 64
    # the rows and key/value heads a grid step of the walk takes: more
    # than one where a head's block is no step's worth of bytes
    step = gqa_decode.choose_step(
        rows, kv_heads, block_k, q.dtype.itemsize, group * block,
        head_dim) if block_k and selected is None else (1, 1)
    # the positions of a block over chosen sets that are gathered and
    # attended at once: their two copies, beside their scores where
    # these are made whole (the plain path)
    tile = 1 if selected is None or block == 1 else _tile_positions(
        block, rows * attended * (
            2 * kv_heads * head_dim * q.dtype.itemsize
            + (0 if block_k else num_heads * 4)), _CHOSEN_TILE_BYTES)
    telemetry.on_cached_attention_lowering(block)
    if attrs.get("shared_readers"):
        telemetry.on_decoder_positions("self", block)
    if readonly:    # it holds no cache: no slots are counted for it
        telemetry.on_cached_attention_readonly_lowering(
            int(attrs.get("reader", 0)), block,
            "kernel" if block_k else "plain", block_k)
    elif selected is None:
        telemetry.on_window_attention_lowering(
            kind, kv_heads, window, "kernel" if block_k else "plain",
            block_k, extent, block, step)
        if diffusion:
            telemetry.on_block_causal_attention_lowering(
                diffusion, block, "kernel" if block_k else "plain")
    else:
        telemetry.on_sparse_attention_lowering(
            kv_heads, attended, extent, "kernel" if block_k else "plain",
            block_k, block, tile)

    with jax.named_scope("kv_write"):
        before = k_cache, v_cache
        if readonly:
            pass    # the caches are another op's: nothing is written
        elif ring_block:
            k_cache, v_cache = (_ring_write(cache, new, pos) for cache, new
                                in ((k_cache, kh), (v_cache, vh)))
        elif writes:    # slots-minor, as the 64-wide kernel reads them
            k_cache, v_cache = gqa_decode.write_step(
                k_cache, v_cache, kh.astype(q.dtype), vh.astype(q.dtype),
                pos)
        else:
            at = pos % window if window else pos
            k_cache = jax.lax.dynamic_update_slice_in_dim(
                k_cache, kh.astype(k_cache.dtype), at, axis=2)
            v_cache = jax.lax.dynamic_update_slice_in_dim(
                v_cache, vh.astype(v_cache.dtype), at, axis=2)

    if selected is not None and block > 1:
        out = _attend_chosen_sets(q, k_cache, v_cache, selected,
                                  ins["Live"][0], kv_heads, sm_scale,
                                  block_k, tile)
        return {"Out": [out], "KCacheOut": [k_cache], "VCacheOut": [v_cache]}
    if selected is not None:
        live = jnp.reshape(ins["Live"][0], (-1,))[0].astype(jnp.int32)
        with jax.named_scope("kv_gather"):
            # an entry is clipped into the extent, not filled in
            # afterwards (a pass over both copies): a dead one may name
            # anything, and what it fetches is masked
            if block_k:
                # whole slots, a slot's heads side by side: where the
                # caches lie heads-minor (a decoder's scan carries them
                # so) the turn is no copy, a slot is one fetch, and the
                # kernel reads the copies as they are written
                at = selected[:, :, None, None].astype(jnp.int32)
                k_live, v_live = (
                    jnp.take_along_axis(jnp.swapaxes(cache, 1, 2), at,
                                        axis=1, mode="clip")
                    for cache in (k_cache, v_cache))
            else:
                at = selected[:, None, :, None].astype(jnp.int32)
                k_live, v_live = (
                    jnp.take_along_axis(cache, at, axis=2, mode="clip")
                    for cache in (k_cache, v_cache))
    else:
        k_live, v_live = k_cache, v_cache

    with jax.named_scope("attn_" + kind):
        # the last live slot: of a ring, all of it once it has wrapped;
        # of a chosen set, the last of its live entries
        last = live - 1 if selected is not None \
            else jnp.minimum(pos, window - 1) if window else pos
        if block_k and selected is not None:
            # copies of a cache in a narrower type are read up
            out = gqa_decode.gqa_decode_chosen(
                qh.reshape(rows, kv_heads, group, head_dim),
                k_live.astype(q.dtype), v_live.astype(q.dtype), live,
                sm_scale, block_k)
        elif block_k:
            # a cache in a narrower type than the products' is read up
            out = gqa_decode.gqa_decode(
                qh.reshape(rows, kv_heads, group * block, head_dim),
                k_live.astype(q.dtype), v_live.astype(q.dtype), last,
                sm_scale, window, block_k, block, step,
                **({"diffusion": diffusion} if diffusion else {}))
        else:
            keys, values, valid = _ring_before_a_block(
                before, (kh, vh), pos) if ring_block \
                else (k_live, v_live, None)
            if group > 1:   # [B, KV, G, T, Dh]: a group beside its head
                qh = qh.reshape(rows, kv_heads, group, block, head_dim)
            highest = jax.lax.Precision.HIGHEST
            s = jnp.einsum("bh...qd,bhkd->bh...qk", qh.astype(jnp.float32),
                           keys.astype(jnp.float32),
                           precision=highest) * sm_scale
            if diffusion:
                # query i attends to the end of its block of B
                valid = jnp.arange(attended)[None, :] <= last + (
                    jnp.arange(block)[:, None] // diffusion * diffusion
                    + (diffusion - 1))
            elif valid is None:
                valid = jnp.arange(attended)[None, :] \
                    <= last + jnp.arange(block)[:, None]
            s = jnp.where(valid[(None,) * (s.ndim - 2)], s, -1e30)
            p = jax.nn.softmax(s, axis=-1)
            out = jnp.einsum("bh...qk,bhkd->bh...qd", p,
                             values.astype(jnp.float32), precision=highest)
        out = out.reshape(rows, num_heads, block, head_dim) \
            .transpose(0, 2, 1, 3).reshape(rows, block, width)
    if readonly:
        return {"Out": [out.astype(q.dtype)]}
    return {"Out": [out.astype(q.dtype)],
            "KCacheOut": [k_cache], "VCacheOut": [v_cache]}


def _attend_chosen_sets(q, k_cache, v_cache, selected, live, kv_heads,
                        sm_scale, chunk, tile):
    """`cached_attention` for a block of T > 1 positions that each attend
    a chosen set: Out [batch, T, heads * head_dim] over the caches with
    the block's entries written, `selected` [batch, T, top_k] and `live`
    [batch, T].  A step's gathers and a step's arithmetic a position, a
    tile of `tile` positions at a time: one gather a cache of the tile's
    [batch, P * top_k] slots, then `gqa_decode_chosen` over batch * P
    rows, a position a row with its own `live` (`chunk` entries a grid
    step), or, with `chunk` 0, the plain products under entry <
    live[b, t]."""
    rows, block, width = q.shape
    top_k, head_dim = selected.shape[-1], k_cache.shape[-1]
    group = width // (kv_heads * head_dim)

    def attend(first, q, selected, live):
        held = selected.shape[1]
        at = selected.reshape(rows, held * top_k).astype(jnp.int32)
        with jax.named_scope("kv_gather"):
            # clipped, and whole slots for the kernel, as a step's
            if chunk:
                k_live, v_live = (
                    jnp.take_along_axis(
                        jnp.swapaxes(cache, 1, 2), at[:, :, None, None],
                        axis=1, mode="clip")
                    .reshape(rows * held, top_k, kv_heads, head_dim)
                    for cache in (k_cache, v_cache))
            else:
                k_live, v_live = (
                    jnp.take_along_axis(cache, at[:, None, :, None], axis=2,
                                        mode="clip")
                    .reshape(rows, kv_heads, held, top_k, head_dim)
                    for cache in (k_cache, v_cache))
        with jax.named_scope("attn_sparse"):
            if chunk:
                from ..kernels import gqa_decode
                out = gqa_decode.gqa_decode_chosen(
                    q.reshape(rows * held, kv_heads, group, head_dim),
                    k_live.astype(q.dtype), v_live.astype(q.dtype),
                    live.reshape(rows * held), sm_scale, chunk)
            else:
                highest = jax.lax.Precision.HIGHEST
                s = jnp.einsum(
                    "bphgd,bhpkd->bphgk", q.astype(jnp.float32).reshape(
                        rows, held, kv_heads, group, head_dim),
                    k_live.astype(jnp.float32), precision=highest) * sm_scale
                valid = jnp.arange(top_k) < live.reshape(rows, held, 1, 1, 1)
                p = jax.nn.softmax(jnp.where(valid, s, -1e30), axis=-1)
                out = jnp.einsum("bphgk,bhpkd->bphgd", p,
                                 v_live.astype(jnp.float32),
                                 precision=highest)
            return out.reshape(rows, held, width).astype(q.dtype)

    return _by_tiles(attend, block, tile, q, selected, live)


def _ring_write(cache, new, pos):
    """The ring [B, KV, window, Dh] after the block `new` [B, KV, T, Dh]
    from position `pos` on: slot s takes the block's last entry j with
    (pos + j) mod window = s, in the ring's type, and keeps what it
    holds where the block has none (T < window)."""
    window, block = cache.shape[2], new.shape[2]
    j = block - 1 - (pos + block - 1 - jnp.arange(window)) % window
    taken = jnp.take(new, jnp.maximum(j, 0), axis=2).astype(cache.dtype)
    return jnp.where((j >= 0)[:, None], taken, cache)


def _ring_before_a_block(caches, new, pos):
    """(keys, values [B, KV, window + T, Dh], valid [T, window + T]) of a
    block of T positions from `pos` on over a ring as it stood before
    the block: the ring's slots, then the block's own entries rounded to
    the ring's type (what a step would read back from its slot).  Slot s
    holds position pos - 1 - (pos - 1 - s) mod window, negative where
    it was never written; query i attends the positions pos + i - window
    + 1 .. pos + i."""
    window, block = caches[0].shape[2], new[0].shape[2]
    held = pos - 1 - (pos - 1 - jnp.arange(window)) % window
    i = jnp.arange(block)[:, None]
    own = jnp.arange(block)[None, :]
    valid = jnp.concatenate(
        [(held >= 0)[None, :] & (held[None, :] > pos + i - window),
         (own <= i) & (own > i - window)], axis=1)
    keys, values = (jnp.concatenate([cache, entry.astype(cache.dtype)],
                                    axis=2)
                    for cache, entry in zip(caches, new))
    return keys, values, valid


def _set_meta(block, name, shape, dtype):
    desc = block.var_recursive(name).desc
    desc.shape, desc.dtype, desc.lod_level = tuple(shape), dtype, 0


def _diff_combine_infer_shape(block, op_desc):
    """`Out` is half as wide as `X`, a pair's two maps made one; a block
    axis the Program leaves open (-1) stays open."""
    x = block.var_recursive(op_desc.input("X")[0]).desc
    shape = tuple(x.shape)
    _set_meta(block, op_desc.output("Out")[0],
              shape[:-1] + (int(shape[-1]) // 2,), x.dtype)


@register_op("diff_combine", stop_gradient_op=True,
             infer_shape=_diff_combine_infer_shape)
def diff_combine_op(ctx, ins, attrs):
    """What differential attention (arXiv:2410.05258) does with a pair
    of heads' two attention maps once each has been applied to the
    pair's values: X [batch, T, 2 * pairs * width] holds, a pair, `P1 V`
    and `P2 V` side by side, `width` values each (the two softmaxes are
    ordinary attention, `cached_attention`'s: this op follows it), and

        lambda = exp(LambdaQ1 . LambdaK1) - exp(LambdaQ2 . LambdaK2)
                 + lambda_init
        Out_p = (1 - lambda_init) * RMSNorm_width(P1 V - lambda * P2 V)

    with the RMSNorm's learned `Scale` [width] and `epsilon`, in float32
    inside: Out [batch, T, pairs * width] in X's type.  The four vectors
    are a layer's (one scalar lambda for all its pairs).  The attr
    `subtract` false leaves the second map out (lambda 0 in the
    difference: a control of a cell's `correct`).  Forward only, as
    `cached_attention`; its scope in a trace is the op's own type."""
    x = ins["X"][0]
    width = int(attrs["width"])
    lambda_init = float(attrs["lambda_init"])
    eps = float(attrs.get("epsilon", 1e-5))
    rows, block, total = x.shape
    if total % (2 * width) or ins["Scale"][0].shape != (width,):
        raise ValueError(
            "diff_combine: X %s is not pairs of two maps of %d values, or "
            "Scale %s is not a pair's norm"
            % (x.shape, width, ins["Scale"][0].shape))
    q1, k1, q2, k2 = (ins[slot][0].astype(jnp.float32) for slot in (
        "LambdaQ1", "LambdaK1", "LambdaQ2", "LambdaK2"))
    lam = jnp.exp(jnp.sum(q1 * k1)) - jnp.exp(jnp.sum(q2 * k2)) \
        + lambda_init
    if not attrs.get("subtract", True):
        lam = 0.0
    maps = x.astype(jnp.float32).reshape(rows, block, -1, 2, width)
    diff = maps[..., 0, :] - lam * maps[..., 1, :]
    normed = diff * jax.lax.rsqrt(
        jnp.mean(jnp.square(diff), axis=-1, keepdims=True) + eps) \
        * ins["Scale"][0].astype(jnp.float32)
    out = (1.0 - lambda_init) * normed
    return {"Out": [out.reshape(rows, block, total // 2).astype(x.dtype)]}


def _index_select_infer_shape(block, op_desc):
    """`Selected` and `Live` follow Q's positions as the Program declares
    them: [batch, top_k] and [batch] for a step declared one position a
    call, [batch, T, top_k] and [batch, T] for a block axis, which stays
    open (-1) where the Program leaves it open."""
    cache = block.var_recursive(op_desc.input("Cache")[0]).desc
    steps = tuple(block.var_recursive(op_desc.input("Q")[0]).desc.shape)[1:2]
    steps = () if steps in ((), (1,)) else steps
    top_k = int(op_desc.attrs["top_k"])
    _set_meta(block, op_desc.output("CacheOut")[0], cache.shape, cache.dtype)
    _set_meta(block, op_desc.output("Selected")[0],
              (cache.shape[0],) + steps + (top_k,), "int32")
    _set_meta(block, op_desc.output("Live")[0], (cache.shape[0],) + steps,
              "int32")


# What one tile of a chooser's block may hold: the index scores of its
# positions before the heads are summed, [batch, P, heads, positions]
# float32 (`mla_index_select`), or the rows its positions' chosen sets
# name, gathered, beside their attention's scores (`mla_cached_attention`
# with `Selected`).  DeepSeek-V3.2's share at 16 rows x 16,384 slots: 67
# MB of index scores a position, four positions a tile; 37.7 MB of
# gathered rows and 16.8 of scores a position, four again
TILE_BYTES = 1 << 28


# What a tile of `cached_attention`'s block over chosen sets may hold: the
# two copies of its positions' sets, which are written by a gather and
# read once by the kernel next to it.  A gather of scattered slots runs at
# the rate of its copy descriptors only while what it writes lies in fast
# memory: at keye-turn-64k-ep8's shape (33.5 MB a position) tiles of 1 and
# 2 positions read 10.8 ns a slot, a step's own rate, 4 read 12.4 and 8
# and 16 read 14.4 (`scripts/gqa_decode_bench.py block`, PERF.md section
# 6, PR 66): half the v5e's 128 MiB of fast memory is what a tile gets
_CHOSEN_TILE_BYTES = 1 << 26


def _tile_positions(block, a_position, within=None):
    """The positions a tile of a block takes: the largest power of two
    whose tile of `a_position` bytes a position stays within `within`
    (`TILE_BYTES` unless given), one at least and the block at most."""
    within = within or TILE_BYTES
    tile = 1
    while 2 * tile <= block and 2 * tile * a_position <= within:
        tile *= 2
    return tile


def _by_tiles(fn, block, tile, *xs):
    """`fn(first, *tiles)` over the block's positions a tile at a time,
    its results [batch, P, ...] side by side along the positions: `xs`
    are [batch, block, ...], a tile is their positions first .. first +
    P - 1.  The whole tiles are one loop (one traced body), what remains
    of a block the tile does not divide is a shorter tile after it."""
    whole, out = block // tile, []
    if whole == 1:
        out.append(fn(0, *(x[:, :tile] for x in xs)))
    elif whole:
        def body(_, first):
            return None, fn(first, *(
                jax.lax.dynamic_slice_in_dim(x, first, tile, axis=1)
                for x in xs))

        tiles = jax.lax.scan(body, None, jnp.arange(whole) * tile)[1]
        out.append(jnp.moveaxis(tiles, 0, 1).reshape(
            (tiles.shape[1], whole * tile) + tiles.shape[3:]))
    if block % tile:
        out.append(fn(whole * tile, *(x[:, whole * tile:] for x in xs)))
    return out[0] if len(out) == 1 else jnp.concatenate(out, axis=1)


@register_op("mla_index_select", stop_gradient_op=True,
             infer_shape=_index_select_infer_shape)
def mla_index_select_op(ctx, ins, attrs):
    """One decode step of a learned chooser of cache slots (the
    "lightning indexer" of DeepSeek-V3.2's sparse attention, as the
    family's released inference code has it), or a block of T
    consecutive steps at once: a token keeps one small key a layer in a
    cache of the chooser's own, and a query's few heads score every live
    slot to pick the `top_k` the attention is to read.

    Q [batch, T, heads * dim] (rotated) and W [batch, T, heads] are the
    index queries and their weights of T >= 1 consecutive tokens of
    every row (T = 1: a decode step; a prompt's prefill feeds many),
    KNew [batch, T, dim] (normed, rotated) their index keys; Cache
    [batch, positions, dim]; Position int [1] or [batch] (lockstep
    rows), the slot the block's first token writes.  The T keys go to
    slots Position .. Position + T - 1 first, then query t scores the
    slots up to its own:

        I_t,s = scale * sum_j W_t,j relu(q_t,j . k_s)    s <= Position + t
        Selected_t = the top_k slots with the largest I_t,s

    The products take their operands in Q's type (a cache in a narrower
    type is read up to it) and add up in float32; relu, the weighted sum
    over the heads and the selection are float32.  A slot past Position
    + t scores -inf and is never among the live ones.  The selection is
    `lax.top_k`'s set (of equal scores the lower slots) and orders
    nothing: kernels/topk_select.py finds the top_k-th largest score by
    counting and writes the chosen slots as they lie in the cache, a row
    of its input a query: batch * T rows of a block, eight a grid step.
    A block's scores before the heads are summed, [batch, T, heads,
    positions] float32, are never whole: they are made a tile of
    positions at a time (`TILE_BYTES`), and what is kept of a tile is
    its [batch, P, positions] sums.
    Scopes: `dsa_index` holds the keys' write and everything that makes
    the scores, `dsa_select` the selection.
    CacheOut is the cache with the slots written (a `ProgramDecoder`
    state pair).  For T = 1: Selected int32 [batch, top_k], the chosen
    set in ascending slot order; Live int32 [batch] = min(top_k,
    Position + 1).  For T > 1 a set a position: Selected [batch, T,
    top_k], Live [batch, T] = min(top_k, Position + t + 1).  Only the
    first Live entries of a set are slots to attend (the live slots have
    the lowest numbers, so they come first), what follows them (where
    fewer slots are live than were asked for) names slots that hold
    nothing and must be masked.  No gradient, as
    `mla_cached_attention`."""
    q, w, k_new = ins["Q"][0], ins["W"][0], ins["KNew"][0]
    cache = ins["Cache"][0]
    pos = jnp.reshape(ins["Position"][0], (-1,))[0].astype(jnp.int32)
    top_k, heads = int(attrs["top_k"]), int(attrs["num_heads"])
    scale = float(attrs.get("scale", 1.0))
    batch, positions, dim = cache.shape
    if q.shape[-1] != heads * dim or w.shape[-1] != heads \
            or k_new.shape[-1] != dim:
        raise ValueError(
            "mla_index_select: the cache holds %d values a token; %d heads "
            "want Q %d wide, W %d and KNew %d, got %s, %s and %s"
            % (dim, heads, heads * dim, heads, dim, q.shape, w.shape,
               k_new.shape))
    if top_k > positions:
        raise ValueError("mla_index_select: top_k %d of a cache of %d "
                         "positions" % (top_k, positions))
    block = q.shape[1]
    tile = _tile_positions(block, batch * heads * positions * 4)
    telemetry.on_mla_index_select_lowering(heads, dim, top_k, cache.dtype,
                                           "count", block, tile)
    f32 = jnp.float32
    if block > 1:
        return _index_select_block(q, w, k_new, cache, pos, heads, top_k,
                                   scale, tile)

    # everything that makes the [batch, positions] scores is one scope:
    # XLA fuses the heads' products into the sum that reads them, and a
    # fusion's time goes to the scope of its root
    with jax.named_scope("dsa_index"):
        cache = jax.lax.dynamic_update_slice_in_dim(
            cache, k_new.reshape(batch, 1, dim).astype(cache.dtype), pos,
            axis=1)
        s = jnp.einsum("bhd,btd->bht", q.reshape(batch, heads, dim),
                       cache.astype(q.dtype), preferred_element_type=f32)
        score = jnp.einsum("bh,bht->bt", w.reshape(batch, heads).astype(f32),
                           jax.nn.relu(s),
                           precision=jax.lax.Precision.HIGHEST)
        if scale != 1.0:
            score = score * scale
        score = jnp.where(jnp.arange(positions) <= pos, score, -jnp.inf)
    # the kernel takes the scores as `dsa_index` leaves them: a custom
    # call is a fusion's boundary, and nothing written here can draw the
    # scores' fusion under this scope
    with jax.named_scope("dsa_select"):
        from ..kernels import topk_select
        selected = topk_select.select_slots(score, top_k)
    live = jnp.full((batch,), jnp.minimum(top_k, pos + 1), jnp.int32)
    return {"CacheOut": [cache], "Selected": [selected], "Live": [live]}


def _index_select_block(q, w, k_new, cache, pos, heads, top_k, scale, tile):
    """`mla_index_select` for a block of T > 1 positions: the step's
    arithmetic a query, the keys written once and read once a tile of
    `tile` positions."""
    batch, positions, dim = cache.shape
    block = q.shape[1]
    f32 = jnp.float32
    with jax.named_scope("dsa_index"):
        cache = jax.lax.dynamic_update_slice_in_dim(
            cache, k_new.astype(cache.dtype), pos, axis=1)
        keys = cache.astype(q.dtype)
        slots = jnp.arange(positions)

        def scores(first, q, w):
            s = jnp.einsum("bphd,bsd->bphs", q, keys,
                           preferred_element_type=f32)
            score = jnp.einsum("bph,bphs->bps", w.astype(f32),
                               jax.nn.relu(s),
                               precision=jax.lax.Precision.HIGHEST)
            if scale != 1.0:
                score = score * scale
            # query t of the block sees slots 0 .. pos + t
            last = pos + first + jnp.arange(q.shape[1])
            return jnp.where(slots <= last[:, None], score, -jnp.inf)

        score = _by_tiles(scores, block, tile,
                          q.reshape(batch, block, heads, dim), w)
    with jax.named_scope("dsa_select"):
        from ..kernels import topk_select
        selected = topk_select.select_slots(
            score.reshape(batch * block, positions), top_k)
    live = jnp.broadcast_to(
        jnp.minimum(top_k, pos + 1 + jnp.arange(block, dtype=jnp.int32)),
        (batch, block))
    return {"CacheOut": [cache],
            "Selected": [selected.reshape(batch, block, top_k)],
            "Live": [live]}


def _mla_infer_shape(block, op_desc):
    q = block.var_recursive(op_desc.input("QNope")[0]).desc
    w_uv = block.var_recursive(op_desc.input("WUv")[0]).desc
    cache = block.var_recursive(op_desc.input("Cache")[0]).desc
    out = block.var_recursive(op_desc.output("Out")[0]).desc
    out.shape, out.dtype, out.lod_level = \
        tuple(q.shape[:-1]) + (w_uv.shape[1],), q.dtype, 0
    kept = block.var_recursive(op_desc.output("CacheOut")[0]).desc
    kept.shape, kept.dtype, kept.lod_level = cache.shape, cache.dtype, 0


@register_op("mla_cached_attention", stop_gradient_op=True,
             infer_shape=_mla_infer_shape)
def mla_cached_attention_op(ctx, ins, attrs):
    """One decode step of multi-head latent attention (DeepSeek-V2,
    arXiv:2405.04434, section 2.1) over a cache of *latents*, or a block
    of T consecutive steps at once: a token and layer keep the normed
    compressed key/value `c` [latent] and the one rotated key `r` [rope]
    that all heads share, side by side, and no head's key or value.

    QNope [batch, T, heads * nope] and QRope [batch, T, heads * rope]
    (rotated) are the queries of T >= 1 consecutive tokens of every row
    (T = 1: a decode step; a prompt's prefill feeds many); CNew [batch,
    T, latent] (normed) and RNew [batch, T, rope] (rotated) their cache
    entries; Cache [batch, positions, latent + rope]; WUk [latent, heads
    * nope] and WUv [latent, heads * value] the up-projections of keys
    and values; Position int [1] or [batch] (lockstep rows), the slot
    the block's first token writes: the entries go to slots Position ..
    Position + T - 1, and query t of the block attends slots 0 ..
    Position + t.

    The up-projections are absorbed, so that no key or value of a head
    is ever made: with k_h = [c W_uk,h | r] and v_h = c W_uv,h,

        q_lat,h = q_nope,h W_uk,h^T                    (`mla_absorb`)
        s_h,t   = (q_lat,h . c_t + q_rope,h . r_t) / sqrt(nope + rope)
        p       = softmax_t(s) over t <= Position      (`mla_scores`)
        o_h     = (sum_t p_h,t c_t) W_uv,h             (`mla_values`)

    which is attention over k_h, v_h exactly.  Both contractions over
    the cache are one batched matrix product each ([T * heads, latent +
    rope] x [positions, latent + rope]^T a row, and [T * heads,
    positions] x [positions, latent + rope], whose last `rope` columns
    are dropped: cheaper than a copy of the cache without them), in the
    cache's type with float32 sums; scores and softmax are float32.  The
    absorbed queries of a block are [batch, T * heads, latent + rope], a
    position after a position: a block reads the weights and a row's
    latents once for all its positions, and is as many times the
    arithmetic of a step.  Out [batch, T, heads * value], CacheOut the
    cache with the slots written: a `ProgramDecoder` state pair.  No
    gradient, as `cached_attention`.

    `sm_scale` (an attr) takes the place of 1 / sqrt(nope + rope): YaRN
    multiplies it by its mscale squared.  `prefill_block` (an attr) is
    the most positions a block of this op was sized for (what a step's
    builder states for `fluid.ProgramDecoder` to prefill by): a longer
    block is refused.  With Selected int32 [batch,
    top_k] and Live int32 [batch] (`mla_index_select`'s) the step
    attends a chosen set and not every slot: the rows Selected names are
    gathered from the cache, after this step's slot is written
    (`dsa_gather`: one [batch, top_k, latent + rope] copy), the same two
    contractions run over the gathered entries, and of a row's top_k
    entries the first Live count, the others are masked: a chosen set is
    a set, the softmax does not care for its order.  A chosen set is one
    position's: a block of T > 1 positions comes with Selected [batch,
    T, top_k] and Live [batch, T], a set a position.  The block's T
    entries are written first, then a tile of P positions at a time
    (`TILE_BYTES`) the rows of the tile's sets are gathered (one gather
    of [batch, P * top_k] rows, of the kind a step makes) and the two
    contractions run over [batch, P, heads, top_k], position t masked by
    Live[b, t]; the queries are absorbed and the values projected up
    once for the whole block.  Nothing is shared between the sets: the
    gathers of a block are those of its T steps, everything else is read
    once.

    With Sink float32 [heads] (a learned sink: one logit a head) the
    softmax's denominator holds exp(Sink_h) beside the attended slots'
    terms, and the sink has no value: p_h,t = exp(s_h,t) / (exp(Sink_h)
    + sum_t' exp(s_h,t')), so a head may attend nothing much.  A step's
    walk takes it as one more term of its last fold; a block of
    positions with a sink keeps the plain products.  A block over
    chosen sets takes it and a gate downstream as a step does.

    Which way the two contractions go (`mla_decode_lowerings_total`):
    kernels/mla_decode.py where it takes the shape, a step over the
    whole extent ("kernel"), a step over its gathered set, which is a
    [batch, top_k, latent + rope] cache whose first Live entries are
    live ("kernel_chosen": the gather's one reader, and no float32
    score array is made), or a block of
    positions over the whole extent without a sink ("kernel"); the
    plain products otherwise ("plain")."""
    q_nope, q_rope = ins["QNope"][0], ins["QRope"][0]
    c_new, r_new = ins["CNew"][0], ins["RNew"][0]
    cache, w_uk, w_uv = ins["Cache"][0], ins["WUk"][0], ins["WUv"][0]
    pos = jnp.reshape(ins["Position"][0], (-1,))[0].astype(jnp.int32)
    selected = (ins.get("Selected") or [None])[0]
    sink = (ins.get("Sink") or [None])[0]
    heads = int(attrs["num_heads"])
    batch, positions, width = cache.shape
    latent, rope_dim = c_new.shape[-1], r_new.shape[-1]
    if latent + rope_dim != width or w_uk.shape[0] != latent:
        raise ValueError(
            "mla_cached_attention: the cache holds %d values a token, "
            "the latent is %d wide and the rotated key %d; W_uk is %s"
            % (width, latent, rope_dim, w_uk.shape))
    block = q_nope.shape[1]
    if selected is not None and tuple(selected.shape[:-1]) \
            != ((batch,) if block == 1 else (batch, block)):
        raise ValueError(
            "mla_cached_attention: Selected %s with a block of %d "
            "positions of %d rows: a chosen set is one position's, "
            "[batch, top_k] for a step and [batch, T, top_k] for a block "
            "(`mla_index_select`'s)" % (selected.shape, block, batch))
    if block > int(attrs.get("prefill_block", 0) or block):
        raise ValueError(
            "mla_cached_attention: a block of %d positions, and the op "
            "was sized for %d (`prefill_block`)"
            % (block, attrs["prefill_block"]))
    nope = q_nope.shape[-1] // heads
    sm_scale = float(attrs.get("sm_scale", 0.0)) \
        or (nope + rope_dim) ** -0.5
    dtype = q_nope.dtype
    # the positions of a block over chosen sets that are gathered and
    # attended at once: their gathered rows beside their scores
    tile = block if selected is None else _tile_positions(
        block, batch * selected.shape[-1] * (
            width * jnp.dtype(dtype).itemsize + heads * 4))
    telemetry.on_mla_cached_attention_lowering(
        heads, latent, rope_dim, cache.dtype,
        "all" if selected is None else selected.shape[-1], block, tile)
    f32 = jnp.float32
    # the walk of the live slots (kernels/mla_decode.py) where what the
    # op sees of its inputs fits it, the plain path otherwise: a step
    # over the whole extent or over its gathered set (a [batch, top_k,
    # width] cache whose first Live entries are live), with a sink or
    # without; a block of positions over the whole extent without one
    from ..kernels import mla_decode
    blocks, path = None, "plain"
    itemsize = jnp.dtype(dtype).itemsize
    if block == 1:
        slots = positions if selected is None else selected.shape[-1]
        if mla_decode.fits(1, slots, latent):
            blocks = mla_decode.choose_blocks(
                batch, heads, slots, width, latent, itemsize,
                whole=selected is not None)
            path = "kernel" if selected is None else "kernel_chosen"
    elif selected is None and sink is None \
            and mla_decode.fits(block, positions, latent):
        blocks = mla_decode.choose_group(
            heads, positions, rope_dim, latent, itemsize)
        path = "kernel"
    telemetry.on_mla_decode_lowering(path, blocks[0] if blocks else 0,
                                     block)

    entry = jnp.concatenate([c_new, r_new], axis=-1).reshape(batch, block,
                                                             width)
    cache = jax.lax.dynamic_update_slice_in_dim(
        cache, entry.astype(cache.dtype), pos, axis=1)
    if selected is not None and block > 1:
        out = _attend_chosen_block(
            q_nope, q_rope, cache, w_uk, w_uv, selected, ins["Live"][0],
            sink, heads, sm_scale, tile)
        return {"Out": [out], "CacheOut": [cache]}
    if selected is None:
        # a cache in a narrower type than the products' is read up to it
        live = cache.astype(dtype)
    else:
        with jax.named_scope("dsa_gather"):
            # an entry is clipped into the extent, not filled in
            # afterwards: the first Live name live slots, and what a
            # dead one fetches is masked.  (The fill is no small thing:
            # the compiler made of it a transposing copy of the whole
            # gathered set in front of the reader, 0.29 ms a step of
            # dsv32-turn-16k-ep16: PERF.md section 6, PR 70.)
            live = jnp.take_along_axis(
                cache, selected[:, :, None].astype(jnp.int32),
                axis=1, mode="clip").astype(dtype)

    if blocks and block > 1:
        # a block through the kernel: the heads are the batch of both
        # products with their matrices, so the queries are made, walked
        # and handed on a head after a head, [heads, batch * T, .], and
        # the rotated part goes beside the latent one, not joined to it
        tokens = batch * block
        with jax.named_scope("mla_absorb"):
            q_lat = jnp.einsum(
                "nhd,chd->hnc", q_nope.reshape(tokens, heads, nope),
                w_uk.reshape(latent, heads, nope).astype(dtype),
                preferred_element_type=f32).astype(dtype)
            q_rot = jnp.swapaxes(
                q_rope.reshape(tokens, heads, rope_dim), 0, 1)
        with jax.named_scope("mla_scores"):
            o_lat = mla_decode.mla_decode_block(q_lat, q_rot, live, pos,
                                                sm_scale, blocks)
        with jax.named_scope("mla_values"):
            out = jnp.einsum(
                "hnc,chd->nhd", o_lat,
                w_uv.reshape(latent, heads, -1).astype(dtype),
                preferred_element_type=f32)
        return {"Out": [out.reshape(batch, block, -1).astype(dtype)],
                "CacheOut": [cache]}

    # a step's queries are a row's heads [batch, heads, .]; a block's
    # keep their position in front of the head, [batch, T, heads, .],
    # for the two products with the heads' matrices, and lie a position
    # after a position, [batch, T * heads, .], over the cache
    lead = (batch, heads) if block == 1 else (batch, block, heads)
    of = "bh" if block == 1 else "bth"
    with jax.named_scope("mla_absorb"):
        q_lat = jnp.einsum(
            "%sd,chd->%sc" % (of, of), q_nope.reshape(lead + (nope,)),
            w_uk.reshape(latent, heads, nope).astype(dtype),
            preferred_element_type=f32).astype(dtype)
        q = jnp.concatenate(
            [q_lat, q_rope.reshape(lead + (rope_dim,))], axis=-1)
        if block > 1:
            q = q.reshape(batch, block * heads, width)
    with jax.named_scope("mla_scores"):
        if blocks:
            # a gathered set's last live entry: a step's rows move in
            # lockstep, Live is one count as Position is one slot
            o_lat = mla_decode.mla_decode(
                q, live, pos if selected is None
                else jnp.reshape(ins["Live"][0], (-1,))[0] - 1, sm_scale,
                latent, blocks, sink)
        else:
            s = jnp.einsum("bhw,btw->bht", q, live,
                           preferred_element_type=f32) * sm_scale
            if selected is not None:
                valid = jnp.arange(selected.shape[-1]) \
                    < jnp.reshape(ins["Live"][0], (-1,))[0]
            elif block == 1:
                valid = jnp.arange(positions) <= pos
            else:   # query row t * heads + h attends slots 0 .. pos + t
                valid = jnp.arange(positions)[None, :] \
                    <= pos + jnp.arange(block * heads)[:, None] // heads
            s = jnp.where(valid[None, None, :] if block == 1
                          else valid[None], s, -1e30)
            if sink is None:
                p = jax.nn.softmax(s, axis=-1)
            else:
                # a query row is a head's (a step) or position t's head
                # h at row t * heads + h (a block)
                p = _softmax_beside_a_sink(
                    s, jnp.tile(sink.astype(f32).reshape(heads), block)[
                        None, :, None])
    with jax.named_scope("mla_values"):
        if not blocks:
            o_lat = jnp.einsum("bht,btw->bhw", p.astype(dtype), live,
                               preferred_element_type=f32)[..., :latent]
        out = jnp.einsum(
            "%sc,chd->%sd" % (of, of),
            o_lat.astype(dtype).reshape(lead + (latent,)),
            w_uv.reshape(latent, heads, -1).astype(dtype),
            preferred_element_type=f32)
    return {"Out": [out.reshape(batch, block, -1).astype(dtype)],
            "CacheOut": [cache]}


def _softmax_beside_a_sink(s, z):
    """softmax of the scores `s` over their last axis with one more term
    in the denominator, exp(`z`) (a sink's logit, shaped to broadcast
    against `s` with a last axis of 1), and none in the result."""
    top = jnp.maximum(jnp.max(s, axis=-1, keepdims=True), z)
    e = jnp.exp(s - top)
    return e / (jnp.sum(e, axis=-1, keepdims=True) + jnp.exp(z - top))


def _attend_chosen_block(q_nope, q_rope, cache, w_uk, w_uv, selected, live,
                         sink, heads, sm_scale, tile):
    """`mla_cached_attention` for a block of T > 1 positions that each
    attend a chosen set: Out [batch, T, heads * value] over `cache` with
    the block's entries written, `selected` [batch, T, top_k] and `live`
    [batch, T].  A step's arithmetic a position; the heads' matrices are
    read once for the block, the sets' rows gathered and attended a tile
    of `tile` positions at a time."""
    batch, block, top_k = selected.shape
    latent, width = w_uk.shape[0], cache.shape[-1]
    dtype, f32 = q_nope.dtype, jnp.float32
    with jax.named_scope("mla_absorb"):
        q_lat = jnp.einsum(
            "bthd,chd->bthc", q_nope.reshape(batch, block, heads, -1),
            w_uk.reshape(latent, heads, -1).astype(dtype),
            preferred_element_type=f32).astype(dtype)
        q = jnp.concatenate(
            [q_lat, q_rope.reshape(batch, block, heads, -1)], axis=-1)
    z = None if sink is None else sink.astype(f32).reshape(heads, 1)

    def attend(first, q, selected, live):
        held = selected.shape[1]
        with jax.named_scope("dsa_gather"):
            # an entry is clipped into the extent, not filled in
            # afterwards (a pass over the copy): a dead one may name
            # anything, and what it fetches is masked
            rows = jnp.take_along_axis(
                cache, selected.reshape(batch, held * top_k, 1)
                .astype(jnp.int32), axis=1, mode="clip").astype(dtype) \
                .reshape(batch, held, top_k, width)
        with jax.named_scope("mla_scores"):
            s = jnp.einsum("bphw,bpkw->bphk", q, rows,
                           preferred_element_type=f32) * sm_scale
            valid = jnp.arange(top_k) < live.reshape(batch, held, 1, 1)
            s = jnp.where(valid, s, -1e30)
            p = jax.nn.softmax(s, axis=-1) if z is None \
                else _softmax_beside_a_sink(s, z)
        with jax.named_scope("mla_values"):
            return jnp.einsum("bphk,bpkw->bphw", p.astype(dtype), rows,
                              preferred_element_type=f32)[
                                  ..., :latent].astype(dtype)

    o_lat = _by_tiles(attend, block, tile, q, selected, live)
    with jax.named_scope("mla_values"):
        out = jnp.einsum("bthc,chd->bthd", o_lat,
                         w_uv.reshape(latent, heads, -1).astype(dtype),
                         preferred_element_type=f32)
    return out.reshape(batch, block, -1).astype(dtype)
