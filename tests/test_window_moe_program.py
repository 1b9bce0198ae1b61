"""Window and full attention over grouped key/value heads through caches
of two shapes (`cached_attention`'s `num_kv_heads` and `window`, the
decode kernel kernels/gqa_decode.py), and the cached step Program built
on them (models/window_moe_program.py) against the plain float32
reference (models/reference/exaone_moe.py): the op against plain masked
attention, a block of positions through a ring against as many single
steps; the kernel under the interpreter against the plain path, a step
and a block of queries; the step driven from empty caches and from a
session handed in against the reference's full forward, and a prompt
prefilled in blocks against the same prompt a position an application;
the shares of an expert layer adding up to the uncut layer; what must
stay as it was (the latent builder's Programs, GPT-2's lowering of
`cached_attention`); the counters.

Tiny sizes on the CPU: 4 layers `LLGL` (the first dense), hidden 64, 4
query heads over 2 key/value heads of 16, window 4, 8 experts scored of
which 4 are held, 2 a token, vocabulary 97, seeded random weights (norm
scales and the selection bias moved off their initial values, so that
one left out shows).
"""

import hashlib

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu.fluid as fluid
from paddle_tpu.kernels import gqa_decode
from paddle_tpu.models.latent_moe_program import (
    build_latent_moe_cached_step_program)
from paddle_tpu.models.reference import exaone_moe as reference
from paddle_tpu.models.window_moe_program import (
    FULL, WINDOW, build_window_moe_cached_step_program,
    window_moe_param_names)
from paddle_tpu.obs import telemetry
from paddle_tpu.ops import registry

B, T, V, W = 3, 14, 97, 4
H, KV, DH, D, FF, FE, E, K, HELD = 4, 2, 16, 64, 128, 32, 8, 2, (2, 4)
LAYERS = (WINDOW, WINDOW, FULL, WINDOW)
MLPS = ("dense", "sparse", "sparse", "sparse")
SIZES = dict(layer_types=LAYERS, mlp_layer_types=MLPS, window=W, n_head=H,
             n_kv_head=KV, d_head=DH, d_model=D, d_ff=FF, d_expert=FE,
             n_experts=E, held=HELD, top_k=K)
CFG = {"num_attention_heads": H, "num_key_value_heads": KV, "head_dim": DH,
       "rms_norm_eps": 1e-5, "rope_parameters": {"rope_theta": 1e6},
       "sliding_window": W, "layer_types": LAYERS,
       "num_experts_per_tok": K, "norm_topk_prob": True,
       "routed_scaling_factor": 2.5}
NAMES = window_moe_param_names(MLPS)
# float32 on the CPU: the step reads caches and a ring, the reference
# makes the whole score matrix under a mask; other sums in another order
LOGITS_RTOL = 1e-5


def _attend(q, k, v, pos, **attrs):
    """One application of the op: (out, k_cache, v_cache)."""
    out = registry.get_op_info("cached_attention").kernel(
        None, {"Q": [q[0]], "KNew": [k[0]], "VNew": [v[0]],
               "KCache": [k[1]], "VCache": [v[1]], "Position": [pos]},
        dict(attrs))
    return out["Out"][0], out["KCacheOut"][0], out["VCacheOut"][0]


def _masked(q, k, v, heads, kv_heads, window):
    """Plain masked attention of whole sequences [B, P, heads * d]."""
    rows, seq, _ = q.shape
    dim = q.shape[-1] // heads
    qh = q.reshape(rows, seq, heads, dim)
    kh, vh = (np.repeat(t.reshape(rows, seq, kv_heads, dim),
                        heads // kv_heads, axis=2) for t in (k, v))
    s = np.einsum("bqhd,bkhd->bhqk", qh, kh) / np.sqrt(dim)
    i, j = np.arange(seq)[:, None], np.arange(seq)[None, :]
    keep = (j <= i) & ((i - j < window) if window else True)
    s = np.where(keep, s, -np.inf)
    p = np.exp(s - s.max(-1, keepdims=True))
    p /= p.sum(-1, keepdims=True)
    return np.einsum("bhqk,bkhd->bqhd", p, vh).reshape(rows, seq, -1)


def _sequence(rs, heads, kv_heads, dim, seq, rows=2):
    return (rs.randn(rows, seq, heads * dim).astype("float32"),
            rs.randn(rows, seq, kv_heads * dim).astype("float32"),
            rs.randn(rows, seq, kv_heads * dim).astype("float32"))


def _through(q, k, v, heads, kv_heads, window, slots, cuts):
    """The sequence through the op, a block of positions an application
    (`cuts`: where the blocks begin)."""
    rows, seq, _ = q.shape
    dim = q.shape[-1] // heads
    caches = [jnp.zeros((rows, kv_heads, slots, dim))] * 2
    outs = []
    for lo, hi in zip(cuts, cuts[1:] + [seq]):
        out, *caches = _attend(
            (q[:, lo:hi],), (k[:, lo:hi], caches[0]),
            (v[:, lo:hi], caches[1]), jnp.full((rows,), lo, jnp.int32),
            num_heads=heads, num_kv_heads=kv_heads, window=window)
        outs.append(np.asarray(out))
    return np.concatenate(outs, axis=1), caches


# -- (a) the op against plain masked attention ---------------------------------

@pytest.mark.parametrize("window,slots,cuts", [
    (4, 4, list(range(20))),            # a ring wrapped five times
    (0, 24, list(range(20))),           # the whole extent, T = 1
    (0, 24, [0, 7, 8, 16]),             # T > 1: the block form
    (0, 24, [0]),                       # one block of all 20
])
def test_grouped_heads_through_the_cache_are_masked_attention(window, slots,
                                                              cuts):
    q, k, v = _sequence(np.random.RandomState(0), 4, 2, 16, 20)
    got, _ = _through(q, k, v, 4, 2, window, slots, cuts)
    np.testing.assert_allclose(got, _masked(q, k, v, 4, 2, window),
                               atol=2e-6)


def test_a_ring_holds_position_p_in_slot_p_mod_window():
    q, k, v = _sequence(np.random.RandomState(1), 4, 2, 16, 11)
    _, (k_cache, _) = _through(q, k, v, 4, 2, 4, 4, list(range(11)))
    want = k.reshape(2, 11, 2, 16).transpose(0, 2, 1, 3)
    for position in range(7, 11):
        np.testing.assert_array_equal(np.asarray(k_cache)[:, :, position % 4],
                                      want[:, :, position])


@pytest.mark.parametrize("why,attrs,slots,block", [
    ("heads that do not group", dict(num_heads=4, num_kv_heads=3), 8, 1),
    ("a cache of other heads", dict(num_heads=4, num_kv_heads=1), 8, 1),
    ("a ring of another size", dict(num_heads=4, num_kv_heads=2, window=4),
     8, 1),
])
def test_what_the_op_cannot_attend_is_refused(why, attrs, slots, block):
    q, k, v = _sequence(np.random.RandomState(2), 4, 2, 16, block)
    cache = jnp.zeros((2, 2, slots, 16))
    with pytest.raises(ValueError, match="cached_attention"):
        _attend((q,), (k, cache), (v, cache), jnp.zeros((2,), jnp.int32),
                **attrs)


@pytest.mark.parametrize("kv_heads", [8, 1])     # groups of 1 and of 8
@pytest.mark.parametrize("start", [0, 2, 5, 13])  # 13: the ring has wrapped
@pytest.mark.parametrize("block", [1, 3, 5, 10])  # 5: the window; 10: + 5
def test_a_block_through_a_ring_is_as_many_single_steps(block, start,
                                                        kv_heads):
    """`start` positions a step at a time, then `block` more as one
    application and as single steps: the same `Out`, and the rings
    slot for slot the same."""
    q, k, v = _sequence(np.random.RandomState(block + start), 8, kv_heads,
                        16, start + block)
    cuts = list(range(start)) or [0]
    steps, step_rings = _through(q, k, v, 8, kv_heads, 5, 5,
                                 list(range(start + block)))
    blocks, block_rings = _through(q, k, v, 8, kv_heads, 5, 5,
                                   sorted(set(cuts + [start])))
    np.testing.assert_allclose(blocks, steps, atol=2e-6)
    np.testing.assert_allclose(blocks, _masked(q, k, v, 8, kv_heads, 5),
                               atol=2e-6)
    for got, want in zip(block_rings, step_rings):
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_a_block_through_a_bfloat16_ring_reads_its_own_keys_as_the_ring_would():
    """The block's own keys and values reach the scores rounded to the
    ring's type, as a step reads them back from its slot."""
    q, k, v = _sequence(np.random.RandomState(8), 4, 2, 16, 9)
    rings = [jnp.zeros((2, 2, 4, 16), jnp.bfloat16)] * 2
    got, *_ = _attend((q,), (k, rings[0]), (v, rings[1]),
                      jnp.zeros((2,), jnp.int32), num_heads=4,
                      num_kv_heads=2, window=4)
    rounded = [np.asarray(jnp.asarray(t, jnp.bfloat16), np.float32)
               for t in (k, v)]
    np.testing.assert_allclose(np.asarray(got),
                               _masked(q, *rounded, 4, 2, 4), atol=2e-6)


# -- (b) the kernel under the interpreter against the plain path ---------------

def _kernel_ins(rs, pos, slots, dtype=jnp.float32, past=0.0, group=4):
    """A step's operands at 128-wide heads: 2 rows, 2 key/value heads;
    the slots past `pos` hold `past`."""
    q = jnp.asarray(rs.randn(2, 2, group, 128), dtype)
    live = (np.arange(slots) <= pos)[None, None, :, None]
    k, v = (jnp.asarray(np.where(live, rs.randn(2, 2, slots, 128), past),
                        dtype) for _ in range(2))
    return q, k, v


def _plain(q, k, v, last, positions=1):
    """The plain masked products for queries [2, 2, group * positions,
    128], row g * positions + t attending slots 0 .. last + t."""
    limit = last + np.arange(q.shape[2]) % positions
    s = jnp.einsum("bhgd,bhkd->bhgk", q.astype(jnp.float32),
                   k.astype(jnp.float32),
                   precision=jax.lax.Precision.HIGHEST) * 128 ** -0.5
    s = jnp.where(jnp.arange(k.shape[2])[None, :] <= limit[:, None], s,
                  -1e30)
    return jnp.einsum("bhgk,bhkd->bhgd", jax.nn.softmax(s, axis=-1),
                      v.astype(jnp.float32),
                      precision=jax.lax.Precision.HIGHEST)


@pytest.mark.parametrize("dtype,atol", [(jnp.float32, 2e-5),
                                        (jnp.bfloat16, 3e-2)])
@pytest.mark.parametrize("pos", [0, 127, 128, 300, 383])
def test_the_walk_of_live_slots_is_the_plain_path(pos, dtype, atol):
    q, k, v = _kernel_ins(np.random.RandomState(pos), pos, 384, dtype)
    got = gqa_decode.gqa_decode(q, k, v, jnp.int32(pos), 128 ** -0.5,
                                block_k=128)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(_plain(q, k, v, pos)), atol=atol)


@pytest.mark.parametrize("pos,block_k", [(0, 128), (5, 128), (127, 128),
                                         (128, 128), (265, 128), (265, 384)])
def test_the_walk_reads_nothing_past_the_position(pos, block_k):
    """NaN in every dead slot: a dead block is neither fetched nor
    computed, and the crossed block's dead slots reach no sum."""
    q, k, v = _kernel_ins(np.random.RandomState(3), pos, 384, past=np.nan)
    got = gqa_decode.gqa_decode(q, k, v, jnp.int32(pos), 128 ** -0.5,
                                block_k=block_k)
    clean = [jnp.nan_to_num(t) for t in (k, v)]
    assert np.isfinite(np.asarray(got)).all()
    np.testing.assert_allclose(np.asarray(got),
                               np.asarray(_plain(q, *clean, pos)), atol=2e-5)


@pytest.mark.parametrize("dtype,atol", [(jnp.float32, 2e-5),
                                        (jnp.bfloat16, 3e-2)])
@pytest.mark.parametrize("positions,last", [
    (2, 0), (2, 127), (2, 128),         # across an edge, and from one
    (16, 0), (16, 120), (16, 256), (16, 368),
    (128, 0), (128, 1), (128, 128), (128, 200), (128, 256),
    (130, 100),                         # more positions than a block holds
])
def test_the_walk_over_a_block_of_queries_is_the_plain_path(positions, last,
                                                            dtype, atol):
    """Slots 0 .. last + positions - 1 are live and the rest hold NaN:
    each of a head's `positions` queries attends its own extent."""
    rs = np.random.RandomState(positions + last)
    q, k, v = _kernel_ins(rs, last + positions - 1, 384, dtype, past=np.nan,
                          group=4 * positions)
    got = gqa_decode.gqa_decode(q, k, v, jnp.int32(last), 128 ** -0.5,
                                block_k=128, positions=positions)
    clean = [jnp.nan_to_num(t) for t in (k, v)]
    np.testing.assert_allclose(
        np.asarray(got, np.float32),
        np.asarray(_plain(q, *clean, last, positions)), atol=atol)


def test_the_ops_kernel_path_is_its_plain_path():
    """128-wide heads: the op walks at T = 1 (ring and whole extent) and
    over a whole extent at T > 1; a block through a ring takes the plain
    path."""
    q, k, v = _sequence(np.random.RandomState(4), 4, 2, 128, 140, rows=1)
    before = telemetry.snapshot()
    ring, _ = _through(q, k, v, 4, 2, 128, 128, list(range(140)))
    full, _ = _through(q, k, v, 4, 2, 0, 256, list(range(140)))
    blocks, _ = _through(q, k, v, 4, 2, 0, 256, [0, 70])
    ring_blocks, _ = _through(q, k, v, 4, 2, 128, 128, [0, 70, 139])
    delta = telemetry.snapshot_delta(before)
    np.testing.assert_allclose(ring, _masked(q, k, v, 4, 2, 128), atol=1e-5)
    np.testing.assert_allclose(full, _masked(q, k, v, 4, 2, 0), atol=1e-5)
    np.testing.assert_allclose(full, blocks, atol=1e-5)
    np.testing.assert_allclose(ring, ring_blocks, atol=1e-5)
    assert {key: n for key, n in delta.items()
            if key.startswith("window_attention_lowerings_total")} == {
        _lowering("window", 2, 128, "kernel", 128): 141,
        _lowering("full", 2, 0, "kernel", 256): 140,
        _lowering("full", 2, 0, "kernel", 256, block=70): 2,
        _lowering("window", 2, 128, "plain", 0, block=70): 1,
        _lowering("window", 2, 128, "plain", 0, block=69): 1}
    assert delta["kv_cache_slots_total{kind=window}"] == 143 * 128
    assert delta["kv_cache_slots_total{kind=full}"] == 142 * 256


def _lowering(kind, kv_heads, window, path, block_k, block=1, step=(1, 1)):
    return "window_attention_lowerings_total{block=%d,block_k=%d,kind=%s," \
        "kv_heads=%d,path=%s,step_heads=%d,step_rows=%d,window=%d}" % (
            block, block_k, kind, kv_heads, path, step[1], step[0], window)


def test_the_kernel_refuses_what_it_does_not_take():
    q, k, v = _kernel_ins(np.random.RandomState(5), 3, 128)
    assert not gqa_decode.fits(8, 100, 128)
    # 64-wide heads: one query a key/value head, 128 slots a tile; a
    # group's rows, a block of positions and any other width are refused
    assert gqa_decode.fits(1, 128, 64)
    assert not gqa_decode.fits(8, 128, 64)
    assert not gqa_decode.fits(1, 100, 64)
    assert not gqa_decode.fits(1, 1024, 32)
    assert gqa_decode.choose_block(32768) == 2048
    assert gqa_decode.choose_block(128) == 128
    # the rows' float32 scores size the block of slots: a group of 8 at
    # 128 positions takes 1024 a step, and 8192 rows fit at no block
    assert gqa_decode.fits(8 * 128, 32768, 128)
    assert gqa_decode.choose_block(32768, 8 * 128) == 1024
    assert gqa_decode.choose_block(32768, 8 * 16) == 2048
    assert not gqa_decode.fits(8 * 1024, 32768, 128)
    with pytest.raises(ValueError, match="gqa_decode"):
        gqa_decode.gqa_decode(q, k, v[:, :, :64], jnp.int32(3), 1.0)
    with pytest.raises(ValueError, match="gqa_decode"):
        gqa_decode.gqa_decode(q, k, v, jnp.int32(3), 1.0, window=64)
    with pytest.raises(ValueError, match="gqa_decode"):   # a ring: T = 1
        gqa_decode.gqa_decode(q, k, v, jnp.int32(3), 1.0, window=128,
                              positions=2)
    with pytest.raises(ValueError, match="gqa_decode"):   # 4 rows by 3
        gqa_decode.gqa_decode(q, k, v, jnp.int32(3), 1.0, positions=3)


# -- (b') 64-wide heads: the kernels over caches as they lie, slots-minor ------

@pytest.mark.parametrize("batch,kv_heads,slots,block_k,step", [
    (48, 16, 1024, 512, (1, 16)),       # gpt2m-decode's
    (48, 16, 768, 256, (2, 16)), (4, 2, 128, 128, (4, 2)),
    (6, 12, 1024, 512, (1, 12)), (3, 32, 640, 128, (3, 16))])
def test_the_chooser_shares_a_step_among_narrow_heads(batch, kv_heads, slots,
                                                      block_k, step):
    """The block is the largest that tiles the extent; the heads and
    the rows that share a grid step divide theirs and keep the step's
    block of a cache within a megabyte."""
    assert gqa_decode.choose_block(slots, 1, 2, 64) == block_k
    assert gqa_decode.choose_step(batch, kv_heads, block_k) == step


def _narrow_ins(rs, pos, slots, kv_heads, dtype, past=np.nan):
    """A step's operands at 64-wide heads, 2 rows: the caches hold
    `past` from slot `pos` on (the step writes slot `pos`)."""
    q, k_new, v_new = (jnp.asarray(rs.randn(2, kv_heads, 1, 64), dtype)
                       for _ in range(3))
    live = (np.arange(slots) < pos)[None, None, :, None]
    k, v = (jnp.asarray(np.where(live, rs.randn(2, kv_heads, slots, 64),
                                 past), dtype) for _ in range(2))
    return q, k_new, v_new, k, v


@pytest.mark.parametrize("dtype,atol", [(jnp.float32, 2e-5),
                                        (jnp.bfloat16, 3e-2)])
@pytest.mark.parametrize("kv_heads", [2, 16])
@pytest.mark.parametrize("pos", [0, 127, 128, 511, 512, 1023])
def test_the_narrow_walk_is_the_plain_path(pos, kv_heads, dtype, atol):
    """2 or 16 heads a grid step, NaN in every slot the step does not
    write or attend: the write sets slot `pos` and no other, the walk
    reads nothing past it, and the values are the plain path's."""
    q, k_new, v_new, k, v = _narrow_ins(np.random.RandomState(pos), pos,
                                        1024, kv_heads, dtype)
    k_out, v_out = gqa_decode.write_step(k, v, k_new, v_new, jnp.int32(pos))
    for got, cache, new in ((k_out, k, k_new), (v_out, v, v_new)):
        np.testing.assert_array_equal(
            np.asarray(got, np.float32),
            np.asarray(jax.lax.dynamic_update_slice_in_dim(
                cache, new, pos, axis=2), np.float32))
    got = gqa_decode.gqa_decode(q, k_out, v_out, jnp.int32(pos), 64 ** -0.5,
                                step=(1, kv_heads))
    s = jnp.einsum("bhgd,bhkd->bhgk", q.astype(jnp.float32),
                   jnp.nan_to_num(k_out).astype(jnp.float32),
                   precision=jax.lax.Precision.HIGHEST) * 64 ** -0.5
    s = jnp.where(jnp.arange(1024) <= pos, s, -1e30)
    want = jnp.einsum("bhgk,bhkd->bhgd", jax.nn.softmax(s, axis=-1),
                      jnp.nan_to_num(v_out).astype(jnp.float32),
                      precision=jax.lax.Precision.HIGHEST)
    assert np.isfinite(np.asarray(got, np.float32)).all()
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want), atol=atol)


def test_the_narrow_kernels_refuse_what_they_do_not_take():
    q, k_new, v_new, k, v = _narrow_ins(np.random.RandomState(6), 3, 128, 2,
                                        jnp.float32, past=0.0)
    with pytest.raises(ValueError, match="gqa_decode"):     # a ring
        gqa_decode.gqa_decode(q, k, v, jnp.int32(3), 1.0, window=128)
    with pytest.raises(ValueError, match="gqa_decode"):     # two rows a head
        gqa_decode.gqa_decode(jnp.concatenate([q, q], axis=2), k, v,
                              jnp.int32(3), 1.0)
    with pytest.raises(ValueError, match="gqa_decode"):     # another type
        gqa_decode.gqa_decode(q, k.astype(jnp.bfloat16),
                              v.astype(jnp.bfloat16), jnp.int32(3), 1.0)
    with pytest.raises(ValueError, match="write_step"):
        gqa_decode.write_step(k, v, k_new.astype(jnp.bfloat16), v_new,
                              jnp.int32(3))
    with pytest.raises(ValueError, match="write_step"):     # 100 slots
        gqa_decode.write_step(k[:, :, :100], v[:, :, :100], k_new, v_new,
                              jnp.int32(3))


def test_the_op_takes_the_narrow_kernels_for_a_step_in_qs_type():
    """16 heads of 64 over 128 slots: a step over caches in Q's type
    writes and walks by the kernels; a block of positions, and a step
    over caches in another type than Q's, take the plain path; all
    three are masked attention."""
    q, k, v = _sequence(np.random.RandomState(9), 16, 16, 64, 12, rows=1)
    before = telemetry.snapshot()
    steps, _ = _through(q, k, v, 16, 16, 0, 128, list(range(12)))
    blocks, _ = _through(q, k, v, 16, 16, 0, 128, [0, 5])
    delta = telemetry.snapshot_delta(before)
    want = _masked(q, k, v, 16, 16, 0)
    np.testing.assert_allclose(steps, want, atol=1e-5)
    np.testing.assert_allclose(blocks, want, atol=1e-5)
    assert {key: n for key, n in delta.items()
            if key.startswith("window_attention_lowerings_total")} == {
        # the 64-wide kernel's grid step takes a row's 16 heads
        _lowering("full", 16, 0, "kernel", 128, step=(1, 16)): 12,
        _lowering("full", 16, 0, "plain", 0, block=5): 1,
        _lowering("full", 16, 0, "plain", 0, block=7): 1}
    caches = [jnp.zeros((1, 16, 128, 64), jnp.bfloat16)] * 2
    before = telemetry.snapshot()
    out, *_ = _attend((q[:, :1],), (k[:, :1], caches[0]),
                      (v[:, :1], caches[1]), jnp.zeros((1,), jnp.int32),
                      num_heads=16)
    assert telemetry.snapshot_delta(before)[
        _lowering("full", 16, 0, "plain", 0)] == 1
    rounded = np.asarray(jnp.asarray(v[:, :1], jnp.bfloat16), np.float32)
    np.testing.assert_allclose(np.asarray(out), rounded, atol=1e-6)


# -- (c) the step Program against the reference's full forward -----------------

def _start(startup, seed=3):
    scope = fluid.Scope()
    exe = fluid.Executor(fluid.CPUPlace())
    startup.random_seed = seed
    exe.run(startup, scope=scope)
    rs = np.random.RandomState(seed)
    for name in jax.tree_util.tree_leaves(NAMES):
        value = np.asarray(scope.get(name))
        if value.ndim == 1:     # norm scales and the selection bias
            scope.set(name, jnp.asarray(
                value + 0.1 * rs.randn(*value.shape).astype("float32")))
    return scope


def _decoder(built, scope, pairs=None):
    return fluid.ProgramDecoder(
        built[0].clone(for_test=True), token_name="tok",
        logits_name=built[2].name,
        state_pairs=built[3] if pairs is None else pairs, scope=scope,
        max_positions=T)


def _empty(dtype=jnp.float32):
    state = {"pos": jnp.zeros((B,), jnp.int32)}
    for i, kind in enumerate(LAYERS):
        for which in "kv":
            state["%s_cache_%d" % (which, i)] = jnp.zeros(
                (B, KV, W if kind == WINDOW else T, DH), dtype)
    return state


def _drive(decoder, tokens, state):
    """[B, P, V]: the step applied position by position."""
    step = decoder._step_fn(decoder._params)
    out = []
    for t in range(tokens.shape[1]):
        logits, state = step(state, jnp.asarray(tokens[:, t]))
        out.append(logits)
    return np.stack([np.asarray(z, np.float32) for z in out], axis=1), state


@pytest.fixture(scope="module")
def built():
    before = telemetry.snapshot()
    program = build_window_moe_cached_step_program(B, T, V, **SIZES)
    at_build = telemetry.snapshot_delta(before)
    scope = _start(program[1])
    decoder = _decoder(program, scope)
    tokens = np.random.RandomState(1).randint(0, V, (B, T)).astype("int32")
    before = telemetry.snapshot()
    got, state = _drive(decoder, tokens, _empty())
    traced = telemetry.snapshot_delta(before)
    params = jax.tree_util.tree_map(scope.get, NAMES)
    want = reference.forward(CFG, params, jnp.asarray(tokens), held=HELD)
    return {"program": program, "scope": scope, "decoder": decoder,
            "tokens": tokens, "got": got, "state": state, "params": params,
            "want": want, "at_build": at_build, "traced": traced}


def _probed(program, scope, max_len):
    """(a decoder that carries every `parts` entry but "counts" out as
    a state pair the step only writes, the state a call starts from)."""
    parts = program[4]
    probes = {"probe.%s_%d" % (key, i): var.name
              for key, found in parts.items() if key != "counts"
              for i, var in enumerate(found)}
    decoder = fluid.ProgramDecoder(
        program[0].clone(for_test=True), token_name="tok",
        logits_name=program[2].name,
        state_pairs=program[3] + list(probes.items()), scope=scope,
        max_positions=max_len)
    state = {f: jnp.zeros((B, KV, W if a.shape[2] == W else max_len, DH))
             if f != "pos" else a for f, a in _empty().items()}
    for feed in probes:
        state[feed] = jnp.zeros((B, K), jnp.int32) if "top_idx" in feed \
            else jnp.zeros((B, K)) if "top_w" in feed \
            else jnp.zeros((B, 1, D))
    return decoder, state


def _prefilled(decoder, state, prompt, takes_block):
    """(state, first token, the counters' rise) of `models.decode.prefill`
    under one jit, in blocks or a position an application."""
    from paddle_tpu.models.decode import prefill

    before = telemetry.snapshot()
    state, first = jax.jit(lambda params, s, p: prefill(
        decoder._step_fn(params), s, p, takes_block))(
            decoder._params, state, jnp.asarray(prompt))
    return state, np.asarray(first), telemetry.snapshot_delta(before)


@pytest.fixture(scope="module")
def long_built():
    """The same sizes at 136 positions: a prompt of more than a
    `PREFILL_BLOCK`."""
    program = build_window_moe_cached_step_program(B, 136, V, **SIZES)
    return program, _start(program[1])


@pytest.mark.parametrize("before,length", [
    (0, 1), (0, 3), (0, W), (0, 9), (0, T), (5, 1), (5, W + 3), (2, 131)])
def test_a_prompt_as_blocks_is_the_prompt_a_position_an_application(
        built, long_built, before, length):
    """From `before` positions already in the caches: the first token,
    the caches, the position and every part (the last position's) of a
    prompt prefilled in blocks against the same prompt a position an
    application; the counters say which form and how many positions."""
    from paddle_tpu.models.decode import PREFILL_BLOCK

    program, scope = (built["program"], built["scope"]) \
        if before + length <= T else long_built
    max_len = T if before + length <= T else 136
    decoder, state = _probed(program, scope, max_len)
    tokens = np.random.RandomState(length).randint(
        0, V, (B, before + length)).astype("int32")
    if before:
        state, _, _ = _prefilled(decoder, state, tokens[:, :before], False)
    want, want_first, by_step = _prefilled(decoder, state, tokens[:, before:],
                                           False)
    got, got_first, by_block = _prefilled(decoder, state, tokens[:, before:],
                                          True)
    np.testing.assert_array_equal(got_first, want_first)
    assert sorted(got) == sorted(want)
    for feed in want:
        if feed == "pos" or "top_idx" in feed:
            np.testing.assert_array_equal(np.asarray(got[feed]),
                                          np.asarray(want[feed]), feed)
        else:
            scale = np.abs(np.asarray(want[feed])).max()
            np.testing.assert_allclose(
                np.asarray(got[feed]), np.asarray(want[feed]),
                atol=1e-5 * scale + 1e-6, err_msg=feed)
    assert int(got["pos"][0]) == before + length
    assert by_step["prefill_lowerings_total{block=1,form=step}"] == 1
    assert by_block["prefill_lowerings_total{block=%d,form=block}"
                    % PREFILL_BLOCK] == 1
    # a remainder first, then the equal blocks' one traced body
    blocks = {n for n in (length % PREFILL_BLOCK,
                          PREFILL_BLOCK * (length >= PREFILL_BLOCK)) if n}
    assert {key: n for key, n in by_block.items()
            if key.startswith("window_attention_lowerings_total")} == dict(
        [(_lowering("window", KV, W, "plain", 0, block=n), 3)
         for n in blocks]
        + [(_lowering("full", KV, 0, "plain", 0, block=n), 1)
           for n in blocks])


def test_the_step_says_it_takes_a_block(built):
    block = built["program"][0].global_block()
    assert tuple(block.var("tok").shape) == (B, -1)
    assert built["decoder"]._takes_block
    for key, found in built["program"][4].items():
        for var in found:
            assert tuple(var.shape) == {
                "counts": (HELD[1],), "top_w": (B, K), "top_idx": (B, K),
            }.get(key, (B, 1, D)), (key, var.shape)


@pytest.mark.parametrize("position", range(T))
def test_step_logits_agree_with_the_reference_at_every_position(built,
                                                                position):
    want = np.asarray(built["want"]["logits"])[:, position]
    np.testing.assert_allclose(built["got"][:, position], want,
                               atol=LOGITS_RTOL * np.abs(want).max())


def test_the_step_declares_a_ring_beside_a_whole_extent(built):
    block = built["program"][0].global_block()
    for i, kind in enumerate(LAYERS):
        for which in "kv":
            name = "%s_cache_%d" % (which, i)
            slots = W if kind == WINDOW else T
            assert tuple(block.var(name).shape) == (B, KV, slots, DH)
            assert built["state"][name].shape == (B, KV, slots, DH)


def test_the_caches_hold_what_the_reference_says_a_session_holds(built):
    want = reference.session(CFG, built["want"], T)
    for name, value in want.items():
        np.testing.assert_allclose(np.asarray(built["state"][name]), value,
                                   atol=1e-5)


@pytest.mark.parametrize("length", [3, W, 9])
def test_a_session_handed_in_continues_as_the_full_forward(built, length):
    """The caches of the first `length` positions as the reference makes
    them (numpy, from the host), then the rest through the step."""
    tokens = built["tokens"]
    found = reference.forward(CFG, built["params"],
                              jnp.asarray(tokens[:, :length]), held=HELD)
    init = reference.session(CFG, found, T)
    got, _ = _drive(built["decoder"], tokens[:, length:],
                    {f: jnp.asarray(a) for f, a in init.items()})
    want = np.asarray(built["want"]["logits"])[:, length:]
    np.testing.assert_allclose(got, want,
                               atol=LOGITS_RTOL * np.abs(want).max())
    # and through the decoder's own call: prompt, then greedy tokens
    toks, _ = built["decoder"].greedy(
        bos=0, eos=V, max_len=1, init_state=init,
        prompt=tokens[:, length:length + 2])
    np.testing.assert_array_equal(
        toks[:, 0], np.argmax(want[:, 1], axis=-1))


def test_a_cache_of_another_extent_than_declared_is_refused(built):
    init = {f: np.asarray(a) for f, a in _empty().items()}
    init["k_cache_0"] = np.zeros((B, KV, T, DH), np.float32)  # no ring
    with pytest.raises(ValueError, match="k_cache_0.*declares"):
        built["decoder"].greedy(bos=0, eos=V, max_len=2, init_state=init)
    with pytest.raises(ValueError, match="exceeds the step program's"):
        built["decoder"].greedy(bos=0, eos=V, max_len=T + 1,
                                init_state=_empty())


def test_the_parts_are_the_references(built):
    """A decoder that carries the parts out of the last step."""
    program, tokens = built["program"], built["tokens"]
    parts = program[4]
    assert len(parts["hidden"]) == len(parts["attn_out"]) == len(LAYERS)
    assert len(parts["moe_out"]) == len(parts["top_idx"]) == 3
    probes = {"probe.attn_%d" % i: var.name
              for i, var in enumerate(parts["attn_out"])}
    decoder = _decoder(program, built["scope"],
                       program[3] + list(probes.items()))
    init = dict(_empty(), **{f: jnp.zeros((B, 1, D)) for f in probes})
    _, _, last = decoder.greedy(
        bos=0, eos=V, max_len=1, init_state=init, prompt=tokens,
        return_state=sorted(probes))
    for i in range(len(LAYERS)):
        want = np.asarray(built["want"]["attn"][i])[:, -1]
        np.testing.assert_allclose(last["probe.attn_%d" % i][:, 0], want,
                                   atol=1e-5 * np.abs(want).max() + 1e-6)


def test_a_bfloat16_cache_stays_near_the_float32_one(built):
    got, state = _drive(built["decoder"], built["tokens"],
                        _empty(jnp.bfloat16))
    assert state["k_cache_2"].dtype == jnp.bfloat16
    want = built["got"]
    assert np.abs(got - want).max() < 0.05 * np.abs(want).max()
    assert np.abs(got - want).max() > 0


# -- (d) the shares of an expert layer add up ---------------------------------

@pytest.mark.parametrize("count", [1, 2, 4])
def test_the_shares_add_up_to_the_uncut_layer(built, count):
    """The guide's share test on the reference the cell is held to: the
    held parts of all E / count shares, the shared expert counted once,
    are the uncut layer's feed-forward."""
    rs = np.random.RandomState(7)
    block = {k: jnp.asarray(v) for k, v in built["params"]["blocks"][1].items()}
    whole = dict(block, **{
        w: jnp.asarray(0.1 * rs.randn(E, *np.asarray(block[w]).shape[1:]),
                       jnp.float32) for w in ("w_gate", "w_up", "w_down")})
    u = jnp.asarray(rs.randn(10, D), jnp.float32)
    with jax.default_matmul_precision("highest"):
        want, _ = reference.feed_forward(CFG, whole, u)
        total = reference.gated(u, whole["shared_in"], whole["shared_out"])
        for first in range(0, E, count):
            share = dict(whole, **{w: whole[w][first:first + count]
                                   for w in ("w_gate", "w_up", "w_down")})
            total = total + reference.feed_forward(CFG, share, u, first,
                                                   shared=False)[0]
    np.testing.assert_allclose(np.asarray(total), np.asarray(want),
                               atol=1e-5)


# -- (e) what the PR leaves as it was -----------------------------------------

def _listing(main):
    return repr([(od.type, sorted((k, tuple(v)) for k, v in od.inputs.items()),
                  sorted((k, tuple(v)) for k, v in od.outputs.items()),
                  sorted((k, repr(v)) for k, v in od.attrs.items()))
                 for od in main.global_block().desc.ops])


@pytest.mark.parametrize("options,digest", [
    ({}, "ad44034b7e904781"),
    (dict(sandwich_norm=False, indexer=(2, 8, 4), n_group=4, topk_group=2,
          router_bias=True, yarn={
              "factor": 40, "original_positions": 4096, "beta_fast": 32,
              "beta_slow": 1, "mscale": 1}), "820727a57c275686"),
])
def test_the_latent_builders_programs_are_op_for_op_what_they_were(options,
                                                                   digest):
    """The feed-forward half is `decoder_block.share_feed_forward` now:
    every op's type, inputs, outputs and attrs, in order.  pangu's
    options build a step that takes a block of positions since PR 53,
    DeepSeek-V3.2's since PR 62: each digest is of the Program that PR
    built (they were 76fca9b464257f60 and 924fd268474f266f until then;
    tests/test_dsv32_program.py holds the new Programs to the old ones'
    products in their order)."""
    main = build_latent_moe_cached_step_program(2, 16, 97, **options)[0]
    assert hashlib.sha256(_listing(main).encode()).hexdigest()[:16] == digest


def _cached_attention_before(q, k_new, v_new, k_cache, v_cache, pos,
                             num_heads):
    """The op's body as it was before it took `num_kv_heads` and
    `window` (commit 92c5422), verbatim."""
    pos = jnp.reshape(pos, (-1,))[0].astype(jnp.int32)
    sm_scale = None
    rows, block, width = q.shape
    extent = k_cache.shape[2]
    qh, kh, vh = (
        x.reshape(rows, block, num_heads, -1).transpose(0, 2, 1, 3)
        for x in (q, k_new, v_new))
    if sm_scale is None:
        sm_scale = qh.shape[-1] ** -0.5
    k_cache = jax.lax.dynamic_update_slice_in_dim(
        k_cache, kh.astype(k_cache.dtype), pos, axis=2)
    v_cache = jax.lax.dynamic_update_slice_in_dim(
        v_cache, vh.astype(v_cache.dtype), pos, axis=2)
    highest = jax.lax.Precision.HIGHEST
    s = jnp.einsum("bhqd,bhkd->bhqk", qh.astype(jnp.float32),
                   k_cache.astype(jnp.float32),
                   precision=highest) * sm_scale
    valid = jnp.arange(extent)[None, :] <= pos + jnp.arange(block)[:, None]
    s = jnp.where(valid[None, None], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhqk,bhkd->bhqd", p, v_cache.astype(jnp.float32),
                     precision=highest)
    out = out.transpose(0, 2, 1, 3).reshape(rows, block, width)
    return out.astype(q.dtype), k_cache, v_cache


@pytest.mark.parametrize("block", [1, 128])
def test_gpt2s_cached_attention_lowers_as_it_did(block):
    """16 heads of 64, no `num_kv_heads`, no `window`, bfloat16 caches,
    a step and a prefill block over an extent the kernels do not take
    (200 slots: no multiple of 128): the plain path is the parent's,
    to the letter.  The decode cell's own instance takes the kernels
    (tests/test_chip_bringup.py)."""
    rows, heads, dim, slots = 2, 16, 64, 200
    like = jax.ShapeDtypeStruct
    args = [like((rows, block, heads * dim), jnp.bfloat16)] * 3 \
        + [like((rows, heads, slots, dim), jnp.bfloat16)] * 2 \
        + [like((rows,), jnp.int32)]

    def now(q, k, v, k_cache, v_cache, pos):
        return _attend((q,), (k, k_cache), (v, v_cache), pos,
                       num_heads=heads, sm_scale=0.0)

    def before(*ins):
        return _cached_attention_before(*ins, num_heads=heads)

    def text(fn):   # less the module's name, which is the function's
        return jax.jit(fn).lower(*args).as_text().split("\n", 1)[1]

    assert text(now) == text(before)


def test_a_program_without_the_new_attrs_says_nothing_of_them():
    from paddle_tpu.models.transformer_program import (
        build_transformer_cached_step_program)

    main = build_transformer_cached_step_program(2, 16, 97, n_layer=1,
                                                 n_head=2, d_model=32,
                                                 d_ff=64)[0]
    ops = [od for od in main.global_block().desc.ops
           if od.type == "cached_attention"]
    assert ops and all(sorted(od.attrs) == ["num_heads", "sm_scale"]
                       for od in ops)


# -- (f) the counters ---------------------------------------------------------

def test_the_build_lowers_nothing(built):
    assert not [k for k in built["at_build"] if "_lowerings_total" in k
                or k.startswith("kv_cache_slots_total")]


def test_counters_say_what_was_lowered(built):
    """One count an op instance a traced step holds; the step was traced
    once a position here (no jit around `_drive`)."""
    traced = built["traced"]
    assert {k: v for k, v in traced.items()
            if k.startswith("window_attention_lowerings_total")} == {
        _lowering("window", KV, W, "plain", 0): 3 * T,
        _lowering("full", KV, 0, "plain", 0): T}
    assert traced["kv_cache_slots_total{kind=window}"] == 3 * T * W
    assert traced["kv_cache_slots_total{kind=full}"] == T * T
    assert traced["cached_attention_lowerings_total{block=1}"] == 4 * T
    assert traced["moe_share_lowerings_total{held=%d,scored=%d,top_k=%d}"
                  % (HELD[1], E, K)] == 3 * T
