"""Flash attention (pallas TPU kernel, online softmax).

No reference counterpart (the 2018 snapshot predates flash attention;
its attention is composed ops — reference: python/paddle/v2/fluid/
nets.py:338 scaled_dot_product_attention materializes the full [T,T]
probability matrix).  This kernel never materializes T×T in HBM: the
grid's innermost axis walks K/V one (block_k, d) tile at a time, the
running max/sum/accumulator live in VMEM scratch across that axis, the
MXU sees [block_q, d] x [d, block_k] matmuls, and the backward pass
recomputes probabilities blockwise (custom VJP, plain XLA).

The kernel compiles through Mosaic when lowered for the TPU and runs
under pallas interpret mode when lowered for the CPU (tests, dry runs);
any other platform is refused at lowering.
"""

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
_LANES = 128


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


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, m_scr, l_scr,
                acc_scr, *, sm_scale, causal, q_offset):
    """One (batch*head, q_block, k_block) grid step.  The k_block axis
    is innermost and sequential: scratch is initialised on its first
    step, folded into on every step and written out on its last."""
    bq, bk = q_ref.shape[0], k_ref.shape[0]
    i, j = pl.program_id(1), pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full(m_scr.shape, NEG_INF, jnp.float32)
        l_scr[...] = jnp.zeros(l_scr.shape, jnp.float32)
        acc_scr[...] = jnp.zeros(acc_scr.shape, jnp.float32)

    def _fold():
        s = jax.lax.dot_general(
            q_ref[...], k_ref[...], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * sm_scale   # [bq, bk]
        if causal:
            q_pos = q_offset + i * bq + jax.lax.broadcasted_iota(
                jnp.int32, (bq, bk), 0)
            k_pos = j * bk + jax.lax.broadcasted_iota(
                jnp.int32, (bq, bk), 1)
            s = jnp.where(q_pos >= k_pos, s, NEG_INF)
        # m/l are [bq, 1] columns here; the scratch keeps them
        # replicated across a vreg's lanes
        m_prev, l_prev = m_scr[:, :1], l_scr[:, :1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        l_new = alpha * l_prev + jnp.sum(p, axis=1, keepdims=True)
        acc_scr[...] = alpha * acc_scr[...] + jnp.dot(
            p.astype(v_ref.dtype), v_ref[...],
            preferred_element_type=jnp.float32)
        m_scr[...] = jnp.broadcast_to(m_new, m_scr.shape)
        l_scr[...] = jnp.broadcast_to(l_new, l_scr.shape)

    if causal:
        # blocks wholly above the diagonal contribute nothing
        pl.when(j * bk <= q_offset + (i + 1) * bq - 1)(_fold)
    else:
        _fold()

    @pl.when(j == pl.num_programs(2) - 1)
    def _finish():
        l = l_scr[:, :1]
        o_ref[...] = (acc_scr[...] / jnp.where(l > 0, l, 1.0)).astype(
            o_ref.dtype)
        # m/l leave as [1, bq] rows: a row is what the (8, 128) tiling
        # rule lets a per-position vector be stored as
        m_ref[...] = m_scr[...].T[:1]
        l_ref[...] = l_scr[...].T[:1]


def _fwd(q, k, v, sm_scale, causal, block_q, block_k, q_offset):
    B, H, Tq, D = q.shape
    Tk = k.shape[2]
    bq = _block(Tq, block_q, "query", q.shape)
    bk = _block(Tk, block_k, "key", k.shape)

    def kv_index(b, i, j):
        if causal:
            # a skipped block re-names the last visible one, so the
            # pipeline does not fetch what the kernel will not read
            j = jnp.minimum(j, (q_offset + (i + 1) * bq - 1) // bk)
        return (b, j, 0)

    call = functools.partial(
        pl.pallas_call,
        functools.partial(_fwd_kernel, sm_scale=sm_scale, causal=causal,
                          q_offset=q_offset),
        grid=(B * H, Tq // bq, Tk // bk),
        in_specs=[
            pl.BlockSpec((None, bq, D), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((None, bk, D), kv_index),
            pl.BlockSpec((None, bk, D), kv_index),
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
            pltpu.VMEM((bq, _LANES), jnp.float32),
            pltpu.VMEM((bq, _LANES), jnp.float32),
            pltpu.VMEM((bq, D), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        name="flash_attention_fwd",
    )
    # chosen by the platform the computation is lowered for, not by the
    # default backend: an export for the TPU from a CPU host gets the
    # Mosaic kernel, a CPUPlace program on a TPU host gets the
    # interpreter, and with no default branch anything else is an error
    o, m, l = jax.lax.platform_dependent(
        q.reshape(B * H, Tq, D), k.reshape(B * H, Tk, D),
        v.reshape(B * H, Tk, D),
        tpu=call(interpret=False), cpu=call(interpret=True))
    return (o.reshape(B, H, Tq, D), m.reshape(B, H, Tq),
            l.reshape(B, H, Tq))


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def flash_attention(q, k, v, sm_scale=None, causal=False, block_q=128,
                    block_k=128, q_offset=0):
    """softmax(q k^T * scale [+ causal mask]) v without materializing
    the score matrix.  q,k,v: [B, H, T, D]; q_offset shifts the causal
    diagonal (used by ring attention where q is a sequence shard)."""
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
        return _bwd(sm_scale, causal, block_k, q_offset, res, do)


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
