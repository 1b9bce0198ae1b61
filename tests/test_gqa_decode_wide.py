"""The 128-wide decode walk where a grid step takes several key/value
heads of a row and several rows of the batch (kernels/gqa_decode.py,
section "128-wide heads, one block no step's worth"): the kernel under
the Pallas interpreter, through `cached_attention`, against the op's
plain path; what `choose_step` answers from the shapes alone; the
refusal of a step that does not divide."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu.fluid  # noqa: F401  (registers the ops)
from paddle_tpu.kernels import gqa_decode
from paddle_tpu.obs import telemetry
from paddle_tpu.ops import registry

D = 128


def _attend(rs, dtype, rows, heads, kv_heads, slots, window, block, position):
    """`cached_attention` over seeded caches: Out as float32."""
    def draw(*shape):
        return jnp.asarray(rs.randn(*shape), dtype)

    ins = {"Q": [draw(rows, block, heads * D)],
           "KNew": [draw(rows, block, kv_heads * D)],
           "VNew": [draw(rows, block, kv_heads * D)],
           "KCache": [draw(rows, kv_heads, slots, D)],
           "VCache": [draw(rows, kv_heads, slots, D)],
           "Position": [jnp.full((rows,), position, jnp.int32)]}
    out = registry.get_op_info("cached_attention").kernel(
        None, ins, {"num_heads": heads, "num_kv_heads": kv_heads,
                    "window": window})
    return np.asarray(out["Out"][0], np.float32)


# (rows, heads, kv heads, slots, window, T, (rows, heads) a step, block)
SHAPES = {
    "one query a head, 30 heads by ten":
        (2, 30, 30, 256, 0, 1, (1, 10), 256),
    "one query a head, 6 heads by six, two rows":
        (4, 6, 6, 256, 0, 1, (2, 6), 128),
    "every row and head in one step":
        (2, 6, 6, 256, 0, 1, (2, 6), 256),
    "a group of two": (2, 8, 4, 256, 0, 1, (2, 2), 128),
    "a block of three positions": (2, 6, 6, 256, 0, 3, (1, 3), 128),
    "a ring": (2, 8, 4, 128, 128, 1, (1, 4), 128),
    "a ring, rows sharing": (4, 8, 4, 128, 128, 1, (2, 2), 128),
}
# a block's first slot, its last slot, mid-block (blocks of 128 and 256
# slots); a ring not wrapped yet, on its last slot, wrapped
POSITIONS = {0: (0, 127, 128, 200, 252), 128: (50, 127, 300)}


@pytest.mark.parametrize("dtype,atol", [(jnp.float32, 3e-5),
                                        (jnp.bfloat16, 3e-2)],
                         ids=["float32", "bfloat16"])
@pytest.mark.parametrize("shape", sorted(SHAPES))
def test_a_shared_step_is_the_ops_plain_path(shape, dtype, atol, monkeypatch):
    """Whatever a grid step takes, the walk gives what the plain
    products under a mask give: operands in Q's type, float32 sums."""
    rows, heads, kv_heads, slots, window, block, step, bk = SHAPES[shape]
    for position in POSITIONS[window]:
        got = {}
        for path in ("kernel", "plain"):
            with monkeypatch.context() as patched:
                patched.setattr(
                    gqa_decode, "choose_block",
                    lambda *a, **k: bk if path == "kernel" else 0)
                patched.setattr(gqa_decode, "choose_step",
                                lambda *a, **k: step)
                before = telemetry.snapshot()
                got[path] = _attend(
                    np.random.RandomState(7), dtype, rows, heads, kv_heads,
                    slots, window, block, position)
                traced = [key for key in telemetry.snapshot_delta(before)
                          if key.startswith("window_attention_lowerings")]
            want = "path=%s,step_heads=%d,step_rows=%d," % (
                (path,) + (step[::-1] if path == "kernel" else (1, 1)))
            assert len(traced) == 1 and want in traced[0], traced
        np.testing.assert_allclose(got["kernel"], got["plain"], atol=atol,
                                   err_msg="position %d" % position)


def test_a_shared_step_is_the_step_of_one():
    """The same folds in the same order a pair: a step of several pairs
    gives bit for bit what a step a pair gives."""
    rs = np.random.RandomState(3)
    q = jnp.asarray(rs.randn(4, 6, 2, D), jnp.bfloat16)
    k, v = (jnp.asarray(rs.randn(4, 6, 256, D), jnp.bfloat16)
            for _ in range(2))
    outs = [np.asarray(gqa_decode.gqa_decode(
        q, k, v, jnp.int32(130), 0.1, block_k=128, step=step), np.float32)
        for step in ((1, 1), (1, 6), (4, 3))]
    np.testing.assert_array_equal(outs[0], outs[1])
    np.testing.assert_array_equal(outs[0], outs[2])


# what the chooser answers from the shapes alone: (batch, key/value
# heads, queries a head, slots, head width, itemsize) -> block, step
CHOSEN = {
    "olmohybrid-decode-pp4's full layers":
        ((128, 30, 1, 512, 128, 2), 512, (1, 10)),
    "Olmo's heads in float32": ((128, 30, 1, 512, 128, 4), 512, (1, 6)),
    "Olmo's heads, four rows": ((4, 30, 1, 512, 128, 2), 512, (1, 10)),
    "Olmo's prefill block of 128 positions":
        ((128, 30, 128, 512, 128, 2), 512, (1, 1)),
    "exaone-turn-32k-ep16's full layers":
        ((8, 8, 8, 32768, 128, 2), 2048, (1, 1)),
    "exaone's prefill block": ((8, 8, 1024, 32768, 128, 2), 1024, (1, 1)),
    "exaone's rings": ((8, 8, 8, 128, 128, 2), 128, (1, 8)),
    "phi4flash-turn-16k's full layers":
        ((16, 10, 4, 16384, 128, 2), 2048, (1, 1)),
    "phi4flash's rings": ((16, 10, 4, 512, 128, 2), 512, (1, 10)),
    "qwen3next-decode-ep16's full layers":
        ((128, 2, 8, 1024, 256, 2), 1024, (1, 1)),
    "keye's gathered heads apart": ((8, 4, 8, 2048, 128, 2), 2048, (1, 1)),
    "too few pairs to share": ((2, 2, 2, 256, 128, 2), 256, (1, 1)),
    "small heads, rows share too": ((64, 2, 1, 128, 128, 2), 128, (8, 2)),
    "gpt2m-decode's 64-wide heads": ((48, 16, 1, 1024, 64, 2), 512, (1, 16)),
}


@pytest.mark.parametrize("case", sorted(CHOSEN))
def test_the_chooser_answers_from_the_shapes_alone(case):
    (batch, kv_heads, rows, slots, dim, itemsize), bk, step = CHOSEN[case]
    assert gqa_decode.choose_block(slots, rows, itemsize, dim) == bk
    assert gqa_decode.choose_step(batch, kv_heads, bk, itemsize, rows,
                                  dim) == step
    assert batch % step[0] == 0 and kv_heads % step[1] == 0
    if dim != 64:   # what the step's pairs hold fits VMEM together
        assert step[0] * step[1] * gqa_decode._vmem_bytes(
            rows, bk, itemsize, dim) <= gqa_decode._VMEM_BYTES
        # and the call keeps grid steps to hide its fetches under
        assert step == (1, 1) or batch * kv_heads // (step[0] * step[1]) \
            >= gqa_decode._WIDE_MIN_STEPS


def test_the_chooser_respects_vmem(monkeypatch):
    """Under the constants as they are a shared step's blocks of both
    caches, double-buffered, are half of what a step may hold; a smaller
    VMEM takes heads off the step."""
    monkeypatch.setattr(gqa_decode, "_VMEM_BYTES", 3 << 20)
    assert gqa_decode.choose_step(128, 30, 512, 2, 1, 128) == (1, 5)
    assert 5 * gqa_decode._vmem_bytes(1, 512, 2) <= 3 << 20


@pytest.mark.parametrize("step", [(1, 4), (3, 1), (0, 2), (1, 2, 1)])
def test_a_step_that_does_not_divide_is_refused(step):
    q = jnp.zeros((4, 6, 1, D), jnp.bfloat16)
    cache = jnp.zeros((4, 6, 256, D), jnp.bfloat16)
    with pytest.raises(ValueError, match="does not divide the 4 rows and 6 "
                                         "key/value heads"):
        gqa_decode.gqa_decode(q, cache, cache, jnp.int32(5), 0.1, step=step)


@pytest.mark.parametrize("step,window,positions,name", [
    ((1, 1), 0, 1, "gqa_decode_k256"), ((1, 3), 0, 1, "gqa_decode_k256_h3"),
    ((2, 6), 0, 1, "gqa_decode_k256_h6_r2"),
    ((1, 2), 256, 1, "gqa_decode_w256_h2"),
    ((1, 2), 0, 2, "gqa_decode_k256_t2_h2")])
def test_the_kernels_name_says_what_a_step_takes(step, window, positions,
                                                 name):
    q = jax.ShapeDtypeStruct((4, 6, positions, D), jnp.bfloat16)
    cache = jax.ShapeDtypeStruct((4, 6, 256, D), jnp.bfloat16)
    module = jax.export.export(jax.jit(
        lambda q, k, v: gqa_decode.gqa_decode(
            q, k, v, jnp.int32(9), 0.1, window, positions=positions,
            step=step)), platforms=["tpu"])(q, cache, cache).mlir_module()
    assert 'kernel_name = "%s"' % name in module
