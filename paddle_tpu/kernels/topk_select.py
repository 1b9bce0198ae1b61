"""The `top_k` largest of a row of float32 scores as a set, its slots in
ascending order, without ordering the scores (a pallas TPU kernel).

    select_slots(score[B, S] float32, top_k) -> int32 [B, top_k]

Row b of the result names exactly the slots `jax.lax.top_k(score,
top_k)` names for it (its total order: -inf < ... < -0.0 < +0.0 < ...;
of equal scores the lower slots), smallest slot first.  `lax.top_k`
over `[8, 65536]` scores is four sorts of the whole extent on the TPU
(0.113 ms each), to learn which 2048 are largest; a chooser's consumers
read the answer as a set.  Here nothing is sorted and nothing is
scattered:

1. *The k-th largest score, by counting.*  A score's bits, with the
   lower 31 flipped where the sign is set, are an int32 key that orders
   as the floats do.  The key `t` of the k-th largest is built a bit a
   pass from the top: "are at least `top_k` keys >= the candidate" is a
   compare and an add a vector register, and the `[8, S]` keys (2 MB at
   65,536 slots) stay in VMEM for all 32 passes.  Eight rows ride the
   sublanes: a register is eight rows x 128 slots, each row against its
   own candidate.
2. *The set.*  Every slot with key > `t`, and of the slots with key ==
   `t` the `top_k - count(key > t)` lowest: the ones below a slot bound
   `u`, found by the same kind of search over the slot's bits, only
   where some row has more keys >= `t` than it may take (ties at the
   threshold: a relu's exact zeros, the -inf past the position).
3. *The list.*  A row's mask is `[C, 128]`, block c of 128 slots a row
   of it (a strided read of the registers the mask lies in).  With
   X[c] the set's entries before block c and P[c, l] those of block c
   before lane l, the j-th entry (from 0) lies in the last block with
   X[c] <= j, at the lane l where X[c] + P[c, l] <= j holds for the last
   time.  So: G^T[l, c] = X[c] + P[c, l] as differences along c (at most
   256 in size: exact in bfloat16), S[c, j] = (X[c] <= j), and one
   product `[128 + 16, C] x [C, top_k]` whose sums telescope to G^T at
   j's block, with rows of ones beneath that count the blocks up to it;
   then `lane = #{l: G <= j} - 1`, a sum down the sublanes.  The counts
   and running counts are products with triangles of ones.  Dense
   operations only, j along the lanes throughout, as the result is
   stored.

Which shapes it takes: all.  The caller's extent is padded to the 128
lanes with -inf (which loses every tie to a real slot, having the
highest numbers), its rows to the 8 sublanes; `top_k` is rounded up to
the lanes inside and cut on the way out.  A grid step is eight rows.

Lowered for the TPU this is a Mosaic kernel named
`topk_select_s<slots>_k<top_k>`; lowered for the CPU the same kernel
runs under the Pallas interpreter (tests), chosen by the platform of
the lowering as kernels/gqa_decode.py's are.
"""

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_LANES = 128
_ROWS = 8       # the sublanes of a float32 register: the rows of a grid step
_INT_MIN = -2 ** 31
# blocks of 128 slots a loop step of a counting pass takes, largest first
_CHUNKS = (16, 8, 4, 2, 1)


def _round_up(n, m):
    return -(-n // m) * m


def _kernel(x_ref, o_ref, k_scr, low_scr, *, blocks, top_k, chunk):
    """Eight rows' scores [8, blocks * 128] -> their chosen slots
    [8, Jp].  `k_scr` int32 [(1 + Cp) * 8, 128] holds block c of all
    eight rows at rows 8 (c + 1) .. 8 (c + 1) + 7: the keys first, the
    set's mask (1 / 0) in their place afterwards; block -1 and the
    blocks past the extent hold zeros.  `low_scr` [Cp, Cp] is the
    triangle (c' < c)."""
    i32, f32, bf16 = jnp.int32, jnp.float32, jnp.bfloat16
    blocks_p = low_scr.shape[0]
    width = o_ref.shape[1]

    # -- the keys ------------------------------------------------------
    k_scr[0:_ROWS, :] = jnp.zeros((_ROWS, _LANES), i32)
    if blocks_p > blocks:
        k_scr[_ROWS * (1 + blocks):, :] = jnp.zeros(
            (_ROWS * (blocks_p - blocks), _LANES), i32)

    def chunks(rows):
        """A row's [8, 128] value beside every block of a loop step."""
        return jnp.concatenate([rows] * chunk, axis=0)

    def at(i):
        return pl.ds(pl.multiple_of(_ROWS * (1 + i * chunk), _ROWS),
                     _ROWS * chunk)

    def keys(i, _):
        bits = lax.bitcast_convert_type(
            x_ref[:, pl.ds(pl.multiple_of(i * (chunk * _LANES), _LANES),
                           chunk * _LANES)], i32)
        key = bits ^ ((bits >> 31) & 0x7fffffff)
        k_scr[at(i), :] = jnp.concatenate(
            [key[:, n:n + _LANES] for n in range(0, chunk * _LANES, _LANES)],
            axis=0)
        return 0

    lax.fori_loop(0, blocks // chunk, keys, 0)

    # slot numbers of a loop step's blocks, less the step's first
    within = (lax.broadcasted_iota(i32, (_ROWS * chunk, _LANES), 0)
              // _ROWS) * _LANES \
        + lax.broadcasted_iota(i32, (_ROWS * chunk, _LANES), 1)

    def count(test):
        """[8, 1] int32: the slots of each row where `test(keys, slots)`
        holds."""
        def body(i, acc):
            return acc + test(k_scr[at(i), :],
                              within + i * (chunk * _LANES)).astype(i32)

        acc = lax.fori_loop(0, blocks // chunk, body,
                            jnp.zeros((_ROWS * chunk, _LANES), i32))
        acc = sum(acc[_ROWS * u:_ROWS * (u + 1)] for u in range(chunk))
        # at most `blocks` a lane and the extent a row: exact in float32
        return jnp.sum(acc.astype(f32), axis=1, keepdims=True).astype(i32)

    def search(bits, admits):
        """The largest value of `bits` bits, a row, that `admits` (which
        holds for 0 and for every value below one it holds for): a bit
        a pass, from the top."""
        def body(i, found):
            candidate = found | jnp.left_shift(jnp.int32(1), bits - 1 - i)
            return jnp.where(admits(candidate), candidate, found)

        return lax.fori_loop(0, bits, body, jnp.zeros((_ROWS, _LANES), i32))

    def at_least(candidate):
        # the search runs over keys + 2**31, which are never negative
        c = chunks(candidate ^ _INT_MIN)
        return count(lambda keys, slots: keys >= c) >= top_k

    tc = chunks(search(32, at_least) ^ _INT_MIN)

    # -- the set -------------------------------------------------------
    def mask_with(bound):
        """The keys give way to the set's mask: above the threshold, or
        at it and below the row's slot `bound`."""
        b = chunks(bound)

        def body(i, _):
            keys = k_scr[at(i), :]
            slots = within + i * (chunk * _LANES)
            k_scr[at(i), :] = (
                (keys > tc) | ((keys == tc) & (slots < b))).astype(i32)
            return 0

        lax.fori_loop(0, blocks // chunk, body, 0)

    tied = jnp.max(count(lambda keys, slots: keys >= tc).astype(f32)) > top_k

    @pl.when(jnp.logical_not(tied))
    def _every_key_at_the_threshold():
        mask_with(jnp.full((_ROWS, _LANES), blocks * _LANES, i32))

    @pl.when(tied)
    def _the_lowest_slots_at_the_threshold():
        above = count(lambda keys, slots: keys > tc)

        def fits(candidate):
            b = chunks(candidate)
            return count(lambda keys, slots: (keys == tc) & (slots < b)) \
                <= top_k - above

        mask_with(search((blocks * _LANES).bit_length(), fits))

    # -- the list ------------------------------------------------------
    lane = lax.broadcasted_iota(i32, (_LANES, _LANES), 1)
    sub = lax.broadcasted_iota(i32, (_LANES, _LANES), 0)
    ones = jnp.ones((_LANES, _LANES), bf16)
    # [l, l'] = (l' < l) beside ones: running counts and counts at once
    before = jnp.concatenate(
        [jnp.where(lane < sub, 1.0, 0.0).astype(bf16), ones], axis=1)
    low_scr[...] = jnp.where(
        lax.broadcasted_iota(i32, (blocks_p, blocks_p), 1)
        < lax.broadcasted_iota(i32, (blocks_p, blocks_p), 0),
        1.0, 0.0).astype(bf16)
    j = lax.broadcasted_iota(i32, (1, width), 1).astype(f32)
    row = lax.broadcasted_iota(i32, (_ROWS, width), 0)

    def one_row(b, out):
        own, prev = (k_scr[pl.ds(b + first, blocks_p, stride=_ROWS), :]
                     .astype(f32) for first in (_ROWS, 0))
        # G^T's differences along c: P[c] - P[c - 1] + count[c - 1]
        steps = lax.dot_general(
            before, jnp.concatenate([own - prev, prev], axis=1).astype(bf16),
            (((1,), (1,)), ((), ())), preferred_element_type=f32)
        counts = jnp.dot(own.astype(bf16), ones, preferred_element_type=f32)
        # X[c] along every lane of row c
        entries_before = jnp.dot(low_scr[...], counts.astype(bf16),
                                 preferred_element_type=f32)
        reached = jnp.concatenate(
            [entries_before <= j[:, n:n + _LANES]
             for n in range(0, width, _LANES)], axis=1)
        # (the rows of ones: a bfloat16 register's 16 sublanes)
        gathered = jnp.dot(
            jnp.concatenate([steps.astype(bf16),
                             jnp.ones((16, blocks_p), bf16)], axis=0),
            jnp.where(reached, 1.0, 0.0).astype(bf16),
            preferred_element_type=f32)
        lanes = jnp.sum((gathered[:_LANES] <= j).astype(f32), axis=0,
                        keepdims=True)
        slots = (gathered[_LANES:_LANES + 1] - 1.0) * _LANES + (lanes - 1.0)
        return jnp.where(row == b, slots.astype(i32), out)

    o_ref[...] = lax.fori_loop(0, _ROWS, one_row,
                               jnp.zeros((_ROWS, width), i32))


# what a grid step may hold in VMEM (a v5e has 128 MiB of it; a kernel
# gets 16 unasked, so the call asks)
_VMEM_BYTES = 96 << 20


def _vmem_bytes(slots, top_k):
    """What a grid step holds in VMEM, twice over for what the compiler
    keeps beside it: the scores (double-buffered), the keys, the
    triangle, (X[c] <= j) and what it is made from, the product's result
    and the output."""
    blocks_p = _round_up(slots // _LANES, _LANES)
    width = _round_up(top_k, _LANES)
    return 2 * (3 * _ROWS * slots * 4 + blocks_p * blocks_p * 2
                + blocks_p * width * (2 + 4) + (2 * _LANES + 32) * width * 4
                + 6 * blocks_p * _LANES * 4)


def _call(score, *, top_k, interpret):
    rows, slots = score.shape
    blocks = slots // _LANES
    blocks_p = _round_up(blocks, _LANES)
    width = _round_up(top_k, _LANES)
    chunk = next(c for c in _CHUNKS if blocks % c == 0)
    return pl.pallas_call(
        functools.partial(_kernel, blocks=blocks, top_k=top_k, chunk=chunk),
        grid=(rows // _ROWS,),
        in_specs=[pl.BlockSpec((_ROWS, slots), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((_ROWS, width), lambda i: (i, 0)),
        scratch_shapes=[
            pltpu.VMEM(((1 + blocks_p) * _ROWS, _LANES), jnp.int32),
            pltpu.VMEM((blocks_p, blocks_p), jnp.bfloat16)],
        out_shape=jax.ShapeDtypeStruct((rows, width), jnp.int32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",),
            vmem_limit_bytes=max(32 << 20, _vmem_bytes(slots, top_k))),
        interpret=interpret,
        # the trace shows which extent and how many slots
        name="topk_select_s%d_k%d" % (slots, top_k),
    )(score)


# Under `jax.jit`, as kernels/gqa_decode.py's entries: the layers of a
# program that hold the same instance share one traced body.
@functools.partial(jax.jit, static_argnames=("top_k",))
def _select(score, top_k):
    call = functools.partial(_call, top_k=top_k)
    return lax.platform_dependent(
        score, tpu=functools.partial(call, interpret=False),
        cpu=functools.partial(call, interpret=True))


def select_slots(score, top_k):
    """int32 [B, top_k]: the slots of the `top_k` largest of each row of
    `score` [B, S] float32 (ties to the lower slots, as `lax.top_k`),
    smallest slot first: see the module's docstring."""
    rows, slots = score.shape
    pad = (_round_up(rows, _ROWS) - rows, _round_up(slots, _LANES) - slots)
    if score.dtype != jnp.float32 or not 0 < top_k <= slots \
            or _vmem_bytes(slots + pad[1], top_k) > _VMEM_BYTES:
        raise ValueError(
            "topk_select.select_slots: %d of %s %s scores a row is no "
            "selection the kernel makes (float32, at most the extent, "
            "eight rows' keys and lists in VMEM)"
            % (top_k, score.shape, score.dtype))
    if any(pad):
        score = jnp.pad(score, ((0, pad[0]), (0, pad[1])),
                        constant_values=-jnp.inf)
    return _select(score, top_k=top_k)[:rows, :top_k]
