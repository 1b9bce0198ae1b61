"""How the flash-attention forward kernel tiles itself from the shapes it
sees (kernels/flash_attention.py: `_choose_blocks`, `_step_bytes`,
`_VMEM_BUDGET`): the chooser as a pure function, the kernel's agreement
with dense attention at the chosen blocks under the Pallas interpreter,
the backward's own block, and the counter that names the tiling."""

import importlib

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from paddle_tpu.obs import telemetry

# the package exports the function under the module's name
fa = importlib.import_module("paddle_tpu.kernels.flash_attention")


# -- the chooser --------------------------------------------------------------

@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("tq,tk,d,dtype", [
    (1024, 1024, 64, jnp.bfloat16),
    (512, 512, 64, jnp.bfloat16),
    (4096, 4096, 128, jnp.bfloat16),
    (32768, 32768, 128, jnp.bfloat16),
    (1024, 1024, 64, jnp.float32),
    (200, 200, 16, jnp.float32),
    (128, 1024, 64, jnp.bfloat16),
])
def test_chosen_blocks_tile_the_sequences_within_the_budget(
        tq, tk, d, dtype, causal):
    itemsize = jnp.dtype(dtype).itemsize
    bq, bk, resident = fa._choose_blocks(
        (8, 16, tq, d), (8, 16, tk, d), itemsize, causal)
    # a block divides its sequence; one that is no multiple of 128 is
    # the whole of a sequence nothing else tiles
    assert tq % bq == 0 and tk % bk == 0
    assert bq % 128 == 0 or bq == tq
    assert bk % 128 == 0 or bk == tk
    kv_rows = tk if resident else bk
    assert fa._step_bytes(bq, bk, kv_rows, d, itemsize) <= fa._VMEM_BUDGET
    if not resident:
        assert fa._step_bytes(bq, bk, tk, d, itemsize) > fa._VMEM_BUDGET
    if (tq, tk, d) == (1024, 1024, 64):
        # the benchmark's shape: 8 x 16 heads; 128 x 128 blocks made
        # 128 x 8 x 8 = 8192 grid steps a call
        assert 8 * 16 * (tq // bq) * (tk // kv_rows) <= 1024


def test_a_named_block_is_kept_beside_a_chosen_one():
    shape = (1, 8, 4096, 128)
    assert fa._choose_blocks(shape, shape, 2, True, 128, 128)[:2] \
        == (128, 128)
    bq, bk, _ = fa._choose_blocks(shape, shape, 2, False, block_q=256)
    assert bq == 256 and bk > 256
    bq, bk, _ = fa._choose_blocks(shape, shape, 2, False, block_k=256)
    assert bk == 256 and bq > 256


def test_a_long_sequence_nothing_tiles_is_refused():
    shape = (1, 8, 32769, 128)
    with pytest.raises(ValueError, match=r"32769.*\(1, 8, 32769, 128\)"):
        fa._choose_blocks(shape, shape, 2, True)
    with pytest.raises(ValueError, match=r"32769.*\(1, 8, 32769, 128\)"):
        fa.flash_attention(*(jnp.zeros(shape, jnp.bfloat16),) * 3)


# -- the kernel at the blocks it chooses --------------------------------------

def _qkv(tq, tk, d=16, heads=2, seed=0):
    rs = np.random.RandomState(seed)

    def mk(t):
        return jnp.asarray(rs.randn(1, heads, t, d).astype(np.float32))

    return mk(tq), mk(tk), mk(tk)


# (Tq, Tk, q_offset): a square of three blocks a side, and the last
# query shard of a longer key side (what ring attention hands over);
# 128 is the only block size that divides any of them
SHAPES = [(384, 384, 0), (384, 640, 256)]
# 128 x 128 blocks at head size 16 in float32 hold 811008 bytes by
# `_step_bytes` with one chunk of K/V in VMEM, 1351680 with 384 keys and
# 1892352 with 640: the first budget keeps K/V resident, the second
# makes the grid walk them
BUDGETS = {"resident": 2000000, "walked": 1000000}


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("tq,tk,q_offset", SHAPES)
@pytest.mark.parametrize("kv", sorted(BUDGETS))
def test_chosen_blocks_match_dense_attention(monkeypatch, kv, tq, tk,
                                             q_offset, causal):
    monkeypatch.setattr(fa, "_VMEM_BUDGET", BUDGETS[kv])
    q, k, v = _qkv(tq, tk)
    bq, bk, resident = fa._choose_blocks(q.shape, k.shape, 4, causal)
    # several blocks on both axes, and the path the budget asks for
    assert tq // bq >= 2 and tk // bk >= 2
    assert resident == (kv == "resident")

    def loss(attention):
        return lambda q, k, v: jnp.sum(jnp.sin(attention(q, k, v)))

    flash = lambda q, k, v: fa.flash_attention(   # noqa: E731
        q, k, v, None, causal, None, None, q_offset)
    dense = lambda q, k, v: fa.reference_attention(   # noqa: E731
        q, k, v, None, causal, q_offset)
    np.testing.assert_allclose(flash(q, k, v), dense(q, k, v), atol=2e-5)
    got = jax.grad(loss(flash), argnums=(0, 1, 2))(q, k, v)
    want = jax.grad(loss(dense), argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(got, want):
        np.testing.assert_allclose(a, b, atol=5e-5)


def test_forward_statistics_keep_their_shapes_and_meaning():
    """(o, m, l) as the backward and ring attention read them: m the row
    maximum of the scaled, masked scores, l the row sum of exp(s - m)."""
    q, k, v = _qkv(256, 256)
    o, m, l = fa._fwd(q, k, v, 0.25, True, None, None, 0)
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k) * 0.25
    s = jnp.where(jnp.tril(jnp.ones((256, 256), bool)), s, fa.NEG_INF)
    assert o.shape == q.shape and m.shape == l.shape == q.shape[:3]
    np.testing.assert_allclose(m, s.max(-1), rtol=1e-6)
    np.testing.assert_allclose(l, jnp.exp(s - m[..., None]).sum(-1),
                               rtol=1e-5)


# -- the backward's block is its own ------------------------------------------

@pytest.mark.parametrize("seq,named,expected", [(256, None, 128),
                                                (64, 16, 16)])
def test_backward_block_does_not_follow_the_forward(monkeypatch, seq,
                                                    named, expected):
    """The scan materialises [B, H, Tq, block_k] float32 tensors: with no
    block named it keeps 128 whatever the forward chose."""
    seen = []
    real = fa._bwd

    def spy(sm_scale, causal, block_k, *rest):
        seen.append(block_k)
        return real(sm_scale, causal, block_k, *rest)

    monkeypatch.setattr(fa, "_bwd", spy)
    q, k, v = _qkv(seq, seq)
    assert fa._choose_blocks(q.shape, k.shape, 4, False)[1] == seq
    jax.grad(lambda q: fa.flash_attention(
        q, k, v, None, False, named, named).sum())(q)
    assert seen == [expected]


# -- the counter names the tiling ---------------------------------------------

def _lowerings(bq, bk, resident):
    return telemetry.snapshot().get(
        "flash_attention_lowerings_total{block_k=%d,block_q=%d,"
        "kv_resident=%s}" % (bk, bq, str(resident).lower()), 0)


@pytest.mark.parametrize("named", [None, 128])
def test_counter_rises_once_per_lowering(named):
    x = jax.ShapeDtypeStruct((8, 16, 1024, 64), jnp.bfloat16)
    labels = fa._choose_blocks(x.shape, x.shape, 2, True, named, named)
    assert labels == ((512, 512, True) if named is None
                      else (128, 128, True))
    before = _lowerings(*labels)
    fn = jax.jit(lambda q, k, v: fa.flash_attention(
        q, k, v, None, True, named, named))
    fn.lower(x, x, x)
    assert _lowerings(*labels) == before + 1
    # forward and backward of one call hold the kernel once
    jax.jit(jax.grad(lambda q, k, v: fn(q, k, v).astype(
        jnp.float32).sum())).lower(x, x, x)
    assert _lowerings(*labels) == before + 2
