"""Flash attention (pallas TPU kernels, online softmax).

No reference counterpart (the 2018 snapshot predates flash attention;
its attention is composed ops — reference: python/paddle/v2/fluid/
nets.py:338 scaled_dot_product_attention materializes the full [T,T]
probability matrix).  These kernels never materialize T×T in HBM: a
grid step of the forward holds one block of queries and a block of K/V
in VMEM and folds the K/V block into the running max, sum and
accumulator one (block_k, block_q) chunk of scores at a time, both
products on the MXU.  The backward (custom VJP) recomputes the
probabilities chunk by chunk from the forward's row statistics, in the
same layout: where a head's queries and its dq fit VMEM, one kernel
holds a block of keys and makes dq, dk and dv together; where they do
not, one kernel holds a block of keys and gathers dk and dv over the
queries that see it, and another holds a block of queries and gathers
dq over the keys it sees.

How much one grid step does is chosen from the shapes
(`_choose_blocks`, `_choose_bwd_blocks`): a grid step costs about
0.5 us empty and every chunk has costs of its own, so the blocks are
as large as the VMEM budget allows, and a causal block at most half its
sequence so that the diagonal still cuts work off.  Where the side a
kernel walks (one head's K and V in the forward, its queries in the
backward) fits the budget beside a chunk it stays resident across the
head's blocks and the chunk loop stops at the causal diagonal; where it
does not, the grid's innermost axis walks it one chunk a step.

The kernels compile through Mosaic when lowered for the TPU and run
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
# the scope the backward's operations lie under, whoever calls `_bwd`:
# the benchmark's readers find them by it
BWD_SCOPE = "flash_attention_bwd"


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


def _fold_chunks(fold, first, last, masked):
    lax.fori_loop(first, last, lambda c, _: fold(c, masked), None)


def _on_platform(call, *args):
    """The Mosaic kernel where the computation is lowered for the TPU,
    the Pallas interpreter where for the CPU.  Chosen by the platform
    of the lowering, not by the default backend: an export for the TPU
    from a CPU host gets the kernel, a CPUPlace program on a TPU host
    the interpreter, and with no default branch anything else is an
    error."""
    return lax.platform_dependent(
        *args, tpu=call(interpret=False), cpu=call(interpret=True))


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

    chunks = kv_rows // bk
    if causal:
        # chunks every query sees whole need no mask; those the
        # diagonal crosses are masked; those above it are never
        # touched.  (lax.div truncates: nothing negative reaches it.)
        whole = lax.min(lax.div(lax.max(lax.add(ahead, 1), 0), bk),
                        chunks)
        seen = lax.min(lax.div(lax.max(lax.add(ahead, bq + bk - 1), 0),
                               bk), chunks)
        _fold_chunks(_fold, 0, whole, False)
        _fold_chunks(_fold, whole, seen, True)
    else:
        _fold_chunks(_fold, 0, chunks, False)

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
    o, m, l = _on_platform(
        call, q.reshape(B * H, Tq, D), k.reshape(B * H, Tk, D),
        v.reshape(B * H, Tk, D))
    return (o.reshape(B, H, Tq, D), m.reshape(B, H, Tq),
            l.reshape(B, H, Tq))


def _flash_fwd_rule(q, k, v, sm_scale, causal, block_q, block_k,
                    q_offset):
    if sm_scale is None:
        sm_scale = q.shape[-1] ** -0.5
    o, m, l = _fwd(q, k, v, sm_scale, causal, block_q, block_k, q_offset)
    # a probability is exp(s - lse): of the two statistics the backward
    # reads only their log-sum-exp; a row that saw no key has l = 0
    lse = m + jnp.log(jnp.where(l > 0, l, 1.0))
    return (o, lse), (q, k, v, o, lse)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def flash_attention_with_lse(q, k, v, sm_scale=None, causal=False,
                             block_q=None, block_k=None, q_offset=0):
    """`flash_attention` and, beside its result, the float32 [B, H, Tq]
    log-sum-exp of every row of scores: what a caller that writes its
    own gradient (`ops/attention.py`) keeps for `_bwd`, so that its
    backward pass need not run the forward kernel again."""
    return _flash_fwd_rule(q, k, v, sm_scale, causal, block_q, block_k,
                           q_offset)[0]


def flash_attention(q, k, v, sm_scale=None, causal=False, block_q=None,
                    block_k=None, q_offset=0):
    """softmax(q k^T * scale [+ causal mask]) v without materializing
    the score matrix.  q,k,v: [B, H, T, D]; q_offset shifts the causal
    diagonal (used by ring attention where q is a sequence shard).
    A block left None is chosen by the kernel from the shapes; one that
    is named must divide its sequence."""
    return flash_attention_with_lse(q, k, v, sm_scale, causal, block_q,
                                    block_k, q_offset)[0]


def _bwd_step_bytes(bq, bk, d, itemsize, tq=None):
    """VMEM bytes one grid step of the backward holds.  Every kernel
    holds four [bk, bq] float32 chunks (scores, probabilities, dp, ds)
    and the casts of two of them, and the lse and delta rows (padded
    and double-buffered as `_step_bytes` says).  With `tq` the one
    kernel that holds all tq queries of a head: their q, do and dq
    [tq, d] and the K, V, dk and dv tiles [bk, d], double-buffered;
    dq^T in float32 [d, tq], its rows padded to 128; K transposed
    [d, bk]; two float32 accumulators [bk, d].  Without, the larger of
    the two kernels that walk: dK/dV holds the tiles of q, do, K, V,
    dk and dv and two accumulators, dQ those of q, do, dq, K and V, K
    transposed and one accumulator."""
    lanes = _pad_to_lanes(d)
    chunk = bq * bk * (4 * 4 + 2 * itemsize)
    if tq is not None:
        return (chunk + 2 * itemsize * lanes * (3 * tq + 4 * bk)
                + 4 * lanes * tq + itemsize * d * bk + 2 * 4 * lanes * bk
                + 2 * 2 * 8 * tq * 4)
    dkv = 2 * itemsize * lanes * (2 * bq + 4 * bk) + 2 * 4 * lanes * bk
    dq = (2 * itemsize * lanes * (3 * bq + 2 * bk) + itemsize * d * bk
          + 4 * lanes * bq)
    return chunk + max(dkv, dq) + 2 * 2 * 8 * bq * 4


def _choose_bwd_blocks(q_shape, k_shape, itemsize, causal, block_q=None,
                       block_k=None):
    """(block_q, block_k, one_kernel) for the backward of one call,
    from what `_choose_blocks` reads: a named block is kept, and the
    chosen pair is the one that holds most scores at a time under the
    VMEM budget (the squarer of two that hold as many), first among
    the pairs that leave room for all of a head's queries and its dq:
    `one_kernel` says one kernel then makes dq, dk and dv together, and
    not one kernel dk and dv and another dq, each walking the other
    side a block a grid step."""
    tq, d = q_shape[2], q_shape[3]
    tk = k_shape[2]
    qs = (_candidates(tq, causal) if block_q is None
          else [_block(tq, block_q, "query", q_shape)])
    ks = (_candidates(tk, causal) if block_k is None
          else [_block(tk, block_k, "key", k_shape)])
    named = block_q is not None and block_k is not None
    pairs = sorted(itertools.product(qs, ks),
                   key=lambda qk: (-qk[0] * qk[1], -min(qk), -qk[0]))
    for one_kernel in (True, False):
        for bq, bk in pairs:
            if _bwd_step_bytes(bq, bk, d, itemsize,
                               tq if one_kernel else None) <= _VMEM_BUDGET:
                return bq, bk, one_kernel
    if named:
        return pairs[0] + (False,)
    raise ValueError(
        "flash_attention: no block among %s tiles query length %d and "
        "key length %d of shapes %s and %s within %d bytes of VMEM in "
        "the backward"
        % (_BLOCKS[::-1], tq, tk, tuple(q_shape), tuple(k_shape),
           _VMEM_BUDGET))


def _bwd_matmul(a, b, rhs_contracts, widen):
    """a . b in float32, `a` first rounded to b's type (p and ds enter
    their second products in the compute type, as the forward's p
    does).  `widen` then takes both to float32, which changes no
    product: XLA's CPU runtime, which runs the interpreter's dots,
    has none for bfloat16 operands."""
    a = lax.convert_element_type(a, b.dtype)
    if widen:
        a, b = (lax.convert_element_type(x, jnp.float32) for x in (a, b))
    return _matmul(a, b, rhs_contracts)


def _bwd_chunk(q, k, v, do, lse, delta, sm_scale, behind, widen):
    """Probabilities and ds of one chunk, both [keys, queries] float32:
    p = exp(s - lse), ds = p * (dp - delta), from the scores k q^T and
    dp = v do^T; lse and delta are [1, queries] rows.  `behind` is None
    where every query sees every key of the chunk, else how far the
    chunk's first key lies ahead of its first query: a query sees the
    keys at or before its own position, and p is 0 elsewhere."""
    s = lax.mul(_bwd_matmul(k, q, 1, widen), sm_scale)
    p = lax.exp(lax.sub(s, lse))
    if behind is not None:
        lead = lax.sub(lax.broadcasted_iota(jnp.int32, s.shape, 1),
                       lax.broadcasted_iota(jnp.int32, s.shape, 0))
        p = lax.select(lax.ge(lead, behind), p, lax.full_like(p, 0))
    ds = lax.mul(p, lax.sub(_bwd_matmul(v, do, 1, widen), delta))
    return p, ds


def _add_dkv(dk_scr, dv_scr, p, ds, q, do, widen):
    dv_scr[...] = lax.add(dv_scr[...], _bwd_matmul(p, do, 0, widen))
    dk_scr[...] = lax.add(dk_scr[...], _bwd_matmul(ds, q, 0, widen))


def _write_dkv(dk_ref, dv_ref, dk_scr, dv_scr, sm_scale):
    dk_ref[...] = lax.convert_element_type(
        lax.mul(dk_scr[...], sm_scale), dk_ref.dtype)
    dv_ref[...] = lax.convert_element_type(dv_scr[...], dv_ref.dtype)


def _write_dq(dq_ref, dqt_scr, sm_scale):
    """dq from dq^T [d padded to 128, queries] in float32 scratch."""
    dq_ref[...] = lax.convert_element_type(
        lax.transpose(lax.mul(dqt_scr[...], sm_scale),
                      (1, 0))[:, :dq_ref.shape[1]], dq_ref.dtype)


def _bwd_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref,
                dk_ref, dv_ref, dqt_scr, kt_scr, dk_scr, dv_scr, *,
                sm_scale, causal, q_offset, widen, bq):
    """One (batch*head, k_block) grid step of the whole backward: the
    step's bk keys meet the head's queries, all in VMEM, bq at a time,
    from the first chunk that sees a key of the block: dv += p do,
    dk += ds q, and dq^T += k^T ds into the head's float32 [d, Tq]
    scratch, which the k_block axis (sequential) fills and its last
    step scales, transposes back and writes.  Five products a chunk:
    the scores and dp are made once for all three gradients.  Chunks
    are [keys, queries] as in `_fwd_kernel`, so the row statistics are
    lane-dense rows and every product takes its operands as they are
    but dq's, which is why K is transposed (once a step) and dq
    accumulates transposed."""
    (bk, d), tq = k_ref.shape, q_ref.shape[0]
    j = pl.program_id(1)
    kt_scr[...] = lax.transpose(k_ref[...], (1, 0))
    dk_scr[...] = lax.full(dk_scr.shape, 0, jnp.float32)
    dv_scr[...] = lax.full(dv_scr.shape, 0, jnp.float32)

    @pl.when(lax.eq(j, 0))
    def _init():
        dqt_scr[...] = lax.full(dqt_scr.shape, 0, jnp.float32)

    # how far this step's first key is ahead of the first query
    behind = lax.sub(lax.mul(j, bk), q_offset)

    def _fold(c, masked):
        if tq == bq:
            # one chunk, read whole: a block that is the whole of a
            # ragged sequence has no aligned slice
            rows = slice(None)
        else:
            rows = pl.ds(pl.multiple_of(lax.mul(c, bq), bq), bq)
        q, do = q_ref[rows, :], do_ref[rows, :]
        p, ds = _bwd_chunk(
            q, k_ref[...], v_ref[...], do, lse_ref[:, rows],
            delta_ref[:, rows], sm_scale,
            lax.sub(behind, lax.mul(c, bq)) if masked else None, widen)
        ds = lax.convert_element_type(ds, q.dtype)
        _add_dkv(dk_scr, dv_scr, p, ds, q, do, widen)
        dqt_scr[:d, rows] = lax.add(
            dqt_scr[:d, rows], _bwd_matmul(kt_scr[...], ds, 0, widen))

    chunks = tq // bq
    if causal:
        # query chunks before the diagonal are never touched; those it
        # crosses are masked; those after it see the block whole
        seen = lax.min(lax.div(lax.max(behind, 0), bq), chunks)
        whole = lax.min(lax.div(lax.max(lax.add(behind, bk + bq - 2), 0),
                                bq), chunks)
        _fold_chunks(_fold, seen, whole, True)
        _fold_chunks(_fold, whole, chunks, False)
    else:
        _fold_chunks(_fold, 0, chunks, False)
    _write_dkv(dk_ref, dv_ref, dk_scr, dv_scr, sm_scale)
    pl.when(lax.eq(j, lax.sub(pl.num_programs(1), 1)))(
        lambda: _write_dq(dq_ref, dqt_scr, sm_scale))


def _fold_where_seen(fold, causal, behind, bq, bk):
    """`fold(masked)` for the one [bk, bq] chunk of a walking kernel's
    grid step, whose first key lies `behind` ahead of its first query:
    unmasked where every query sees every key, masked where the
    diagonal crosses the chunk, not at all above it."""
    if not causal:
        return fold(False)
    whole = lax.le(lax.add(behind, bk - 1), 0)
    pl.when(whole)(lambda: fold(False))
    pl.when(lax.bitwise_and(lax.bitwise_not(whole),
                            lax.le(behind, bq - 1)))(lambda: fold(True))


def _bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                    dk_ref, dv_ref, dk_scr, dv_scr, *, sm_scale, causal,
                    q_offset, widen):
    """One (batch*head, k_block, q_block) grid step of dK and dV where
    a head's queries do not fit VMEM: dv += p do and dk += ds q in
    float32 scratch for the step's bk keys and bq queries.  The q_block
    axis is innermost and sequential: the scratch is zeroed on its
    first step and written, dk scaled, on its last."""
    bq, bk = q_ref.shape[0], k_ref.shape[0]
    j, i = pl.program_id(1), pl.program_id(2)

    @pl.when(lax.eq(i, 0))
    def _init():
        dk_scr[...] = lax.full(dk_scr.shape, 0, jnp.float32)
        dv_scr[...] = lax.full(dv_scr.shape, 0, jnp.float32)

    behind = lax.sub(lax.mul(j, bk), lax.add(lax.mul(i, bq), q_offset))

    def _fold(masked):
        q, do = q_ref[...], do_ref[...]
        p, ds = _bwd_chunk(q, k_ref[...], v_ref[...], do, lse_ref[...],
                           delta_ref[...], sm_scale,
                           behind if masked else None, widen)
        _add_dkv(dk_scr, dv_scr, p, ds, q, do, widen)

    _fold_where_seen(_fold, causal, behind, bq, bk)
    pl.when(lax.eq(i, lax.sub(pl.num_programs(2), 1)))(
        lambda: _write_dkv(dk_ref, dv_ref, dk_scr, dv_scr, sm_scale))


def _bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref,
                   acc_scr, *, sm_scale, causal, q_offset, widen):
    """One (batch*head, q_block, k_block) grid step of dQ where a
    head's keys do not fit VMEM, shaped as `_fwd_kernel`'s walk:
    dq^T += k^T ds as [d, bq] float32 for the step's bq queries and bk
    keys; the k_block axis is innermost and sequential, and its last
    step scales dq, transposes it back and writes it."""
    (bq, d), bk = q_ref.shape, k_ref.shape[0]
    i, j = pl.program_id(1), pl.program_id(2)

    @pl.when(lax.eq(j, 0))
    def _init():
        acc_scr[...] = lax.full(acc_scr.shape, 0, jnp.float32)

    behind = lax.sub(lax.mul(j, bk), lax.add(lax.mul(i, bq), q_offset))

    def _fold(masked):
        k = k_ref[...]
        _, ds = _bwd_chunk(q_ref[...], k, v_ref[...], do_ref[...],
                           lse_ref[...], delta_ref[...], sm_scale,
                           behind if masked else None, widen)
        acc_scr[:d] = lax.add(
            acc_scr[:d],
            _bwd_matmul(lax.transpose(k, (1, 0)),
                        lax.convert_element_type(ds, k.dtype), 0, widen))

    _fold_where_seen(_fold, causal, behind, bq, bk)
    pl.when(lax.eq(j, lax.sub(pl.num_programs(2), 1)))(
        lambda: _write_dq(dq_ref, acc_scr, sm_scale))


def _flash_bwd_rule(sm_scale, causal, block_q, block_k, q_offset, res,
                    cotangents):
    """The flash-attention VJP as kernels that recompute the
    probabilities chunk by chunk from the forward's statistics:
    dv = p^T do; dp = do v^T; ds = p * (dp - rowsum(do * o));
    dq = ds k; dk = ds^T q.  Where a head's queries and its dq fit VMEM
    one kernel holds a block of keys and makes all three (five products
    a chunk); where they do not, one kernel holds a block of keys and
    gathers dk and dv over the queries that see it and another holds a
    block of queries and gathers dq over the keys it sees (seven).
    Products take their operands in the type they arrive in and
    accumulate in float32; nothing the size of the score square reaches
    HBM.  A cotangent of the log-sum-exp enters with the row sums:
    d lse / d s = p, so ds = p * (dp - (rowsum(do * o) - dlse))."""
    q, k, v, o, lse = res
    do, dlse = cotangents
    with jax.named_scope(BWD_SCOPE):
        delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32),
                        axis=-1) - dlse
        return _bwd(q, k, v, do, lse, delta, sm_scale, causal, block_q,
                    block_k, q_offset)


def _bwd(q, k, v, do, lse, delta, sm_scale, causal, block_q, block_k,
         q_offset=0):
    """dq, dk, dv of one call from the forward's row statistics `lse`
    and the row sums `delta` of do * o, both float32 [B, H, Tq], at
    blocks chosen here as the forward chooses its own: what the
    custom VJP above and the `flash_attention` op's gradient
    (`ops/attention.py`), which saved `lse`, both end in."""
    if sm_scale is None:
        sm_scale = q.shape[-1] ** -0.5
    bq, bk, one_kernel = _choose_bwd_blocks(
        q.shape, k.shape, q.dtype.itemsize, causal, block_q, block_k)
    for kernel in (("dq_dkv",) if one_kernel else ("dkv", "dq")):
        telemetry.on_flash_attention_bwd_lowering(kernel, bq, bk)
    return _bwd_kernels(q, k, v, do, lse, delta, sm_scale=sm_scale,
                        causal=causal, q_offset=q_offset, bq=bq, bk=bk,
                        one_kernel=one_kernel)


@functools.partial(jax.jit, static_argnames=(
    "sm_scale", "causal", "q_offset", "bq", "bk", "one_kernel"))
def _bwd_kernels(q, k, v, do, lse, delta, *, sm_scale, causal, q_offset,
                 bq, bk, one_kernel):
    """dq, dk, dv from the row statistics and do, at blocks already
    chosen.  Under `jax.jit` so that a program holding the same
    attention many times (one a layer) traces these kernels once, and
    the build's shape inference, the executor's program and the
    functional step share that trace: a kernel body is some hundred
    primitives and a step program would trace it twice an op (PERF.md
    section 6, PR 26)."""
    B, H, Tq, D = q.shape
    Tk = k.shape[2]
    operands = [x.reshape(B * H, x.shape[2], D) for x in (q, k, v, do)] \
        + [x.reshape(B * H, 1, Tq) for x in (lse, delta)]
    name = "flash_attention_bwd%%s_q%d_k%d" % (bq, bk)
    lanes = _pad_to_lanes(D)

    def call(kernel, interpret, **args):
        return pl.pallas_call(
            functools.partial(kernel, sm_scale=sm_scale, causal=causal,
                              q_offset=q_offset, widen=interpret),
            interpret=interpret, **args)

    def out(x):
        return jax.ShapeDtypeStruct((B * H,) + x.shape[2:], x.dtype)

    if one_kernel:
        def head(b, j):
            return (b, 0, 0)

        def block(b, j):
            return (b, j, 0)

        dq, dk, dv = _on_platform(functools.partial(
            call, functools.partial(_bwd_kernel, bq=bq),
            grid=(B * H, Tk // bk),
            in_specs=[pl.BlockSpec((None, Tq, D), head),
                      pl.BlockSpec((None, bk, D), block),
                      pl.BlockSpec((None, bk, D), block),
                      pl.BlockSpec((None, Tq, D), head),
                      pl.BlockSpec((None, 1, Tq), head),
                      pl.BlockSpec((None, 1, Tq), head)],
            out_specs=[pl.BlockSpec((None, Tq, D), head),
                       pl.BlockSpec((None, bk, D), block),
                       pl.BlockSpec((None, bk, D), block)],
            out_shape=[out(q), out(k), out(v)],
            scratch_shapes=[pltpu.VMEM((lanes, Tq), jnp.float32),
                            pltpu.VMEM((D, bk), k.dtype),
                            pltpu.VMEM((bk, D), jnp.float32),
                            pltpu.VMEM((bk, D), jnp.float32)],
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "arbitrary")),
            name=name % ""), *operands)
        return dq.reshape(q.shape), dk.reshape(k.shape), dv.reshape(v.shape)

    def q_index(b, j, i):
        if causal:
            # a q block before the first that sees this k block
            # re-names that one: it is fetched once, not per step
            first = lax.div(lax.max(lax.sub(lax.mul(j, bk), q_offset), 0),
                            bq)
            i = lax.max(i, lax.min(first, Tq // bq - 1))
        return i

    def k_index(b, i, j):
        if causal:
            last = lax.add(lax.mul(i, bq), q_offset + bq - 1)
            j = lax.min(j, lax.div(lax.max(last, 0), bk))
        return (b, j, 0)

    def q_tile(index):
        return [pl.BlockSpec((None, bq, D),
                             lambda *g: (g[0], index(*g), 0)),
                pl.BlockSpec((None, 1, bq),
                             lambda *g: (g[0], 0, index(*g)))]

    q_walked, stats_walked = q_tile(q_index)
    q_held, stats_held = q_tile(lambda b, i, j: i)
    k_held = pl.BlockSpec((None, bk, D), lambda b, j, i: (b, j, 0))
    k_walked = pl.BlockSpec((None, bk, D), k_index)
    walk = dict(dimension_semantics=("parallel", "parallel", "arbitrary"))
    dk, dv = _on_platform(functools.partial(
        call, _bwd_dkv_kernel, grid=(B * H, Tk // bk, Tq // bq),
        in_specs=[q_walked, k_held, k_held, q_walked, stats_walked,
                  stats_walked],
        out_specs=[k_held, k_held], out_shape=[out(k), out(v)],
        scratch_shapes=[pltpu.VMEM((bk, D), jnp.float32),
                        pltpu.VMEM((bk, D), jnp.float32)],
        compiler_params=pltpu.CompilerParams(**walk),
        name=name % "_dkv"), *operands)
    dq = _on_platform(functools.partial(
        call, _bwd_dq_kernel, grid=(B * H, Tq // bq, Tk // bk),
        in_specs=[q_held, k_walked, k_walked, q_held, stats_held,
                  stats_held],
        out_specs=q_held, out_shape=out(q),
        scratch_shapes=[pltpu.VMEM((lanes, bq), jnp.float32)],
        compiler_params=pltpu.CompilerParams(**walk),
        name=name % "_dq"), *operands)
    return dq.reshape(q.shape), dk.reshape(k.shape), dv.reshape(v.shape)


flash_attention_with_lse.defvjp(_flash_fwd_rule, _flash_bwd_rule)


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
