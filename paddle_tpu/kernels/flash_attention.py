"""Flash attention (pallas TPU kernel, online softmax).

No reference counterpart (the 2018 snapshot predates flash attention;
its attention is composed ops — reference: python/paddle/v2/fluid/
nets.py:338 scaled_dot_product_attention materializes the full [T,T]
probability matrix).  This kernel never materializes T×T in HBM: a grid
step holds one block of queries and a block of K/V in VMEM and folds
the K/V block into the running max, sum and accumulator one (block_k,
block_q) chunk of scores at a time, both products run on the MXU, and
the backward pass recomputes probabilities blockwise (custom VJP, plain
XLA).

How much one grid step does is chosen from the shapes
(`_choose_blocks`): a grid step costs about 0.5 us empty and every
fold of a chunk has costs of its own, so the blocks are as large as
the VMEM budget allows, and a causal block at most half its sequence
so that the diagonal still cuts work off.  Where one head's K and V
fit the budget beside a chunk they stay resident across the head's
query blocks and the chunk loop ends at the causal diagonal; where
they do not, the grid's innermost axis walks them one chunk a step.

The kernel compiles through Mosaic when lowered for the TPU and runs
under pallas interpret mode when lowered for the CPU (tests, dry runs);
any other platform is refused at lowering.
"""

import functools
import itertools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..obs import telemetry

NEG_INF = -1e30
_LANES = 128
# what one grid step may hold in VMEM (`_step_bytes`); under Mosaic's
# default scoped limit of 16 MiB, so no limit has to be asked for
_VMEM_BUDGET = 14 * 2 ** 20
# the block sizes the chooser tries: multiples of the MXU's 128 rows
_BLOCKS = (1024, 512, 256, 128)
# the backward's key block when nobody names one
_BWD_BLOCK_K = 128


def _block(seq, block, what, shape):
    """The block size along one sequence axis.  It must divide the
    sequence: the grid has no ragged last tile, and shrinking the block
    until it fits would hand the MXU slivers without saying so."""
    block = min(block, seq)
    if seq % block:
        raise ValueError(
            "flash_attention: %s length %d of shape %s is not a multiple "
            "of its block size %d" % (what, seq, tuple(shape), block))
    return block


def _candidates(seq, causal):
    """Block sizes to try along one axis, largest first: those that
    divide the sequence, else the whole of one that fits in a block.  A
    causal block is at most half its sequence: with one block nothing
    lies above the diagonal, and every row of a block walks as far as
    its last row does."""
    most = max(_LANES, seq // 2) if causal else seq
    found = [b for b in _BLOCKS if b <= most and seq % b == 0]
    return found or ([seq] if seq <= _BLOCKS[0] else [])


def _pad_to_lanes(n):
    return -(-n // _LANES) * _LANES


def _step_bytes(bq, bk, kv_rows, d, itemsize):
    """VMEM bytes one grid step holds: the q and o tiles [bq, d] and
    the K and V blocks [kv_rows, d], each double-buffered by the
    pipeline, their minor dimension padded to 128 lanes; V transposed
    [d, kv_rows]; the float32 accumulator, its rows padded likewise;
    the m and l rows [1, bq] (a row pads to 8 sublanes, and they are
    double-buffered too); one chunk's scores and probabilities [bk, bq]
    in float32 and the probabilities cast for the second product."""
    lanes = _pad_to_lanes(d)
    tiles = 2 * itemsize * lanes * (2 * bq + 2 * kv_rows)
    scratch = itemsize * d * kv_rows + 4 * lanes * bq
    stats = 2 * 2 * 8 * bq * 4
    chunk = bq * bk * (4 + 4 + itemsize)
    return tiles + scratch + stats + chunk


def _choose_blocks(q_shape, k_shape, itemsize, causal, block_q=None,
                   block_k=None):
    """(block_q, block_k, kv_resident) for one call, from what the
    kernel sees: the sequence lengths, the head size, the item size and
    whether the mask is causal.  A block the caller names is kept as it
    is; what is chosen is the pair that folds most scores at a time
    under the VMEM budget, with half its block_k if all of one head's K
    and V then fit beside the fold: `kv_resident` says a grid step
    holds them all, not one block_k chunk of them."""
    tq, d = q_shape[2], q_shape[3]
    tk = k_shape[2]
    qs = (_candidates(tq, causal) if block_q is None
          else [_block(tq, block_q, "query", q_shape)])
    ks = (_candidates(tk, causal) if block_k is None
          else [_block(tk, block_k, "key", k_shape)])
    named = block_q is not None and block_k is not None
    fit = [(bq, bk) for bq, bk in sorted(
        itertools.product(qs, ks), key=lambda qk: (-qk[0] * qk[1], -qk[0]))
        if named or _step_bytes(bq, bk, bk, d, itemsize) <= _VMEM_BUDGET]
    if not fit:
        raise ValueError(
            "flash_attention: no block among %s tiles query length %d and "
            "key length %d of shapes %s and %s within %d bytes of VMEM"
            % (_BLOCKS[::-1], tq, tk, tuple(q_shape), tuple(k_shape),
               _VMEM_BUDGET))
    bq, bk = fit[0]
    for smaller in (bk, bk // 2):
        if (bq, smaller) in fit and _step_bytes(
                bq, smaller, tk, d, itemsize) <= _VMEM_BUDGET:
            return bq, smaller, True
    return bq, bk, False


def _matmul(a, b, rhs_contracts=0):
    return lax.dot_general(a, b, (((1,), (rhs_contracts,)), ((), ())),
                           preferred_element_type=jnp.float32)


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_scr, vt_scr,
                *, sm_scale, causal, q_offset, bk, resident):
    """One (batch*head, q_block, kv_block) grid step: the K/V block in
    VMEM (all of the head's keys, or one chunk of them) is folded, bk
    keys at a time, into the running max and sum, which are the m and l
    output blocks themselves, and into the float32 accumulator.  The
    kv_block axis is innermost and sequential: the three are
    initialised on its first step, and o is written on its last.

    The scores are held transposed, [keys, queries], and so are the
    accumulator and V, [d, positions].  The softmax's reductions then
    run down the sublanes, elementwise from vreg to vreg, and the
    per-query statistics are [1, bq] rows, dense in the lanes; held as
    [queries, keys] every statistic is a [bq, 1] column of one useful
    lane a vreg and every reduction crosses the lanes.  V is transposed
    into scratch once a head where its keys are resident (the q_block
    axis is sequential for that), else once a step; o is transposed
    back as it is written.

    Written in lax primitives, not jnp: a step program holds this body
    once per attention op and pass, and every jnp call or operator on a
    tracer is a jitted function to trace besides."""
    (bq, d), kv_rows = q_ref.shape, k_ref.shape[0]
    i, j = pl.program_id(1), pl.program_id(2)

    def _transpose_v():
        vt_scr[...] = lax.transpose(v_ref[...], (1, 0))

    if resident:
        pl.when(lax.eq(i, 0))(_transpose_v)
    else:
        _transpose_v()

    @pl.when(lax.eq(j, 0))
    def _init():
        m_ref[...] = lax.full(m_ref.shape, NEG_INF, jnp.float32)
        l_ref[...] = lax.full(l_ref.shape, 0, jnp.float32)
        acc_scr[...] = lax.full(acc_scr.shape, 0, jnp.float32)

    # how far this step's first query is ahead of its first key
    ahead = lax.sub(lax.add(lax.mul(i, bq), q_offset),
                    lax.mul(j, kv_rows))

    def _fold(c, masked):
        if kv_rows == bk:
            # one chunk, read whole: a block that is the whole of a
            # ragged sequence has no aligned slice
            k, vt = k_ref[...], vt_scr[...]
        else:
            keys = pl.ds(pl.multiple_of(lax.mul(c, bk), bk), bk)
            k, vt = k_ref[keys, :], vt_scr[:, keys]
        s = lax.mul(_matmul(k, q_ref[...], 1), sm_scale)     # [bk, bq]
        if masked:
            # a query sees the keys at or before its own position
            lead = lax.sub(lax.broadcasted_iota(jnp.int32, s.shape, 1),
                           lax.broadcasted_iota(jnp.int32, s.shape, 0))
            s = lax.select(
                lax.ge(lead, lax.sub(lax.mul(c, bk), ahead)), s,
                lax.full_like(s, NEG_INF))
        m_prev = m_ref[...]                                  # [1, bq]
        m_new = lax.max(m_prev,
                        lax.expand_dims(lax.reduce_max(s, (0,)), (0,)))
        alpha = lax.exp(lax.sub(m_prev, m_new))
        p = lax.exp(lax.sub(s, m_new))
        l_ref[...] = lax.add(
            lax.mul(alpha, l_ref[...]),
            lax.expand_dims(lax.reduce_sum(p, (0,)), (0,)))
        acc_scr[:d] = lax.add(
            lax.mul(alpha, acc_scr[:d]),
            _matmul(vt, lax.convert_element_type(p, vt.dtype)))
        m_ref[...] = m_new

    def _fold_chunks(first, last, masked):
        lax.fori_loop(first, last, lambda c, _: _fold(c, masked), None)

    chunks = kv_rows // bk
    if causal:
        # chunks every query sees whole need no mask; those the
        # diagonal crosses are masked; those above it are never
        # touched.  (lax.div truncates: nothing negative reaches it.)
        whole = lax.min(lax.div(lax.max(lax.add(ahead, 1), 0), bk),
                        chunks)
        seen = lax.min(lax.div(lax.max(lax.add(ahead, bq + bk - 1), 0),
                               bk), chunks)
        _fold_chunks(0, whole, False)
        _fold_chunks(whole, seen, True)
    else:
        _fold_chunks(0, chunks, False)

    @pl.when(lax.eq(j, lax.sub(pl.num_programs(2), 1)))
    def _finish():
        l = l_ref[...]
        o = lax.div(acc_scr[...],
                    lax.select(lax.gt(l, 0.0), l, lax.full_like(l, 1)))
        o_ref[...] = lax.convert_element_type(
            lax.transpose(o, (1, 0))[:, :d], o_ref.dtype)


def _fwd(q, k, v, sm_scale, causal, block_q, block_k, q_offset):
    B, H, Tq, D = q.shape
    Tk = k.shape[2]
    bq, bk, resident = _choose_blocks(q.shape, k.shape, q.dtype.itemsize,
                                      causal, block_q, block_k)
    kv_rows = Tk if resident else bk
    telemetry.on_flash_attention_lowering(bq, bk, resident)

    def kv_index(b, i, j):
        if causal:
            # a skipped block re-names the last visible one, so the
            # pipeline does not fetch what the kernel will not read
            last = lax.add(lax.mul(i, bq), q_offset + bq - 1)
            j = lax.min(j, lax.div(lax.max(last, 0), kv_rows))
        return (b, j, 0)

    call = functools.partial(
        pl.pallas_call,
        functools.partial(_fwd_kernel, sm_scale=sm_scale, causal=causal,
                          q_offset=q_offset, bk=bk, resident=resident),
        grid=(B * H, Tq // bq, Tk // kv_rows),
        in_specs=[
            pl.BlockSpec((None, bq, D), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((None, kv_rows, D), kv_index),
            pl.BlockSpec((None, kv_rows, D), kv_index),
        ],
        out_specs=[
            pl.BlockSpec((None, bq, D), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((None, 1, bq), lambda b, i, j: (b, 0, i)),
            pl.BlockSpec((None, 1, bq), lambda b, i, j: (b, 0, i)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B * H, Tq, D), q.dtype),
            jax.ShapeDtypeStruct((B * H, 1, Tq), jnp.float32),
            jax.ShapeDtypeStruct((B * H, 1, Tq), jnp.float32),
        ],
        scratch_shapes=[
            # the accumulator [d, bq], its rows padded to 128 so that
            # the last step can transpose it; V transposed
            pltpu.VMEM((_pad_to_lanes(D), bq), jnp.float32),
            pltpu.VMEM((D, kv_rows), v.dtype),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=(
                "parallel", "arbitrary" if resident else "parallel",
                "arbitrary")),
        # the trace shows which tiling ran; readers match the prefix
        name="flash_attention_fwd_q%d_k%d%s"
             % (bq, bk, "_kvres" if resident else ""),
    )
    # chosen by the platform the computation is lowered for, not by the
    # default backend: an export for the TPU from a CPU host gets the
    # Mosaic kernel, a CPUPlace program on a TPU host gets the
    # interpreter, and with no default branch anything else is an error
    o, m, l = lax.platform_dependent(
        q.reshape(B * H, Tq, D), k.reshape(B * H, Tk, D),
        v.reshape(B * H, Tk, D),
        tpu=call(interpret=False), cpu=call(interpret=True))
    return (o.reshape(B, H, Tq, D), m.reshape(B, H, Tq),
            l.reshape(B, H, Tq))


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def flash_attention(q, k, v, sm_scale=None, causal=False, block_q=None,
                    block_k=None, q_offset=0):
    """softmax(q k^T * scale [+ causal mask]) v without materializing
    the score matrix.  q,k,v: [B, H, T, D]; q_offset shifts the causal
    diagonal (used by ring attention where q is a sequence shard).
    A block left None is chosen by the kernel from the shapes; one that
    is named must divide its sequence."""
    if sm_scale is None:
        sm_scale = q.shape[-1] ** -0.5
    o, _, _ = _fwd(q, k, v, sm_scale, causal, block_q, block_k, q_offset)
    return o


def _flash_fwd_rule(q, k, v, sm_scale, causal, block_q, block_k,
                    q_offset):
    if sm_scale is None:
        sm_scale = q.shape[-1] ** -0.5
    o, m, l = _fwd(q, k, v, sm_scale, causal, block_q, block_k, q_offset)
    return o, (q, k, v, o, m, l)


def _flash_bwd_rule(sm_scale, causal, block_q, block_k, q_offset, res,
                    do):
    """Blockwise recompute backward (the standard flash-attention VJP):
    dv = p^T do; dp = do v^T; ds = p*(dp - rowsum(do*o)); dq = ds k;
    dk = ds^T q.  Runs as plain XLA over k-blocks via scan — the
    recompute keeps memory at O(T*block) like the forward."""
    with jax.named_scope("flash_attention_bwd"):
        # the forward's chosen block is not the backward's: the scan
        # materialises [B, H, Tq, block_k] float32 tensors in HBM
        return _bwd(sm_scale, causal,
                    _BWD_BLOCK_K if block_k is None else block_k,
                    q_offset, res, do)


def _bwd(sm_scale, causal, block_k, q_offset, res, do):
    q, k, v, o, m, l = res
    if sm_scale is None:
        sm_scale = q.shape[-1] ** -0.5
    B, H, Tq, D = q.shape
    Tk = k.shape[2]
    bk = _block(Tk, block_k, "key", k.shape)

    safe_l = jnp.where(l > 0, l, 1.0)
    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32),
                    axis=-1)                           # [B,H,Tq]
    qs = q.astype(jnp.float32) * sm_scale
    q_pos = q_offset + jnp.arange(Tq)

    def per_block(carry, i):
        dq = carry
        k_blk = jax.lax.dynamic_slice_in_dim(k, i * bk, bk, axis=2)
        v_blk = jax.lax.dynamic_slice_in_dim(v, i * bk, bk, axis=2)
        s = jnp.einsum("bhqd,bhkd->bhqk", qs, k_blk.astype(jnp.float32))
        if causal:
            k_pos = i * bk + jnp.arange(bk)
            mask = q_pos[:, None] >= k_pos[None, :]
            s = jnp.where(mask, s, NEG_INF)
        p = jnp.exp(s - m[..., None]) / safe_l[..., None]   # [B,H,q,k]
        dv_blk = jnp.einsum("bhqk,bhqd->bhkd", p,
                            do.astype(jnp.float32))
        dp = jnp.einsum("bhqd,bhkd->bhqk", do.astype(jnp.float32),
                        v_blk.astype(jnp.float32))
        ds = p * (dp - delta[..., None])                    # [B,H,q,k]
        dq = dq + jnp.einsum("bhqk,bhkd->bhqd", ds,
                             k_blk.astype(jnp.float32)) * sm_scale
        dk_blk = jnp.einsum("bhqk,bhqd->bhkd", ds, qs)
        return dq, (dk_blk, dv_blk)

    nblocks = Tk // bk
    dq0 = jnp.zeros(q.shape, jnp.float32)
    dq, (dks, dvs) = jax.lax.scan(per_block, dq0, jnp.arange(nblocks))
    dk = jnp.moveaxis(dks, 0, 2).reshape(B, H, Tk, D)
    dv = jnp.moveaxis(dvs, 0, 2).reshape(B, H, Tk, D)
    return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype)


flash_attention.defvjp(_flash_fwd_rule, _flash_bwd_rule)


def reference_attention(q, k, v, sm_scale=None, causal=False, q_offset=0):
    """Dense O(T^2)-memory attention for parity tests."""
    if sm_scale is None:
        sm_scale = q.shape[-1] ** -0.5
    s = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * sm_scale
    if causal:
        Tq, Tk = q.shape[2], k.shape[2]
        mask = (q_offset + jnp.arange(Tq))[:, None] >= jnp.arange(Tk)
        s = jnp.where(mask[None, None], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", p,
                      v.astype(jnp.float32)).astype(q.dtype)
