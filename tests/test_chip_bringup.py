"""What the TPU bring-up settled, as far as a CPU can check it: the flash
kernel lowers for the TPU through Mosaic, no entry point hides the
device it ran on, and the compile cache has one fixed place."""

import os
import re
import subprocess
import sys
import time

import jax
import jax.numpy as jnp
import pytest

import paddle_tpu.fluid as fluid
from paddle_tpu.kernels.flash_attention import flash_attention

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(argv, env_update=None, env_drop=(), timeout=300):
    env = {k: v for k, v in os.environ.items() if k not in env_drop}
    env.update(env_update or {})
    return subprocess.run([sys.executable] + argv, cwd=REPO, env=env,
                          capture_output=True, text=True, timeout=timeout)


def _json_lines(text):
    return [line for line in text.splitlines() if line.startswith("{")]


# -- the kernel the compiler accepts ---------------------------------------

@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("shape", [(2, 8, 512, 64), (1, 8, 4096, 128),
                                   (8, 16, 1024, 64), (1, 8, 32768, 128)])
def test_flash_attention_lowers_for_tpu(shape, causal):
    """Forward and backward lower for the TPU platform from this CPU
    host, and what they lower to is the Mosaic kernel — not the pallas
    interpreter the CPU tests run.  Every refusal of the Pallas TPU
    lowering (block tiling, unimplemented primitives) surfaces here, at
    the blocks the kernel chooses for the smoke's shapes and for the
    benchmark's gpt2m-train, and at a length whose K/V and queries the
    grids of all three kernels walk."""
    x = jax.ShapeDtypeStruct(shape, jnp.bfloat16)

    def forward(q, k, v):
        return flash_attention(q, k, v, None, causal)

    def backward(q, k, v):
        return jax.grad(
            lambda *qkv: forward(*qkv).astype(jnp.float32).sum(),
            argnums=(0, 1, 2))(q, k, v)

    for fn in (forward, backward):
        module = jax.export.export(
            jax.jit(fn), platforms=["tpu"])(x, x, x).mlir_module()
        assert "tpu_custom_call" in module


@pytest.mark.parametrize("shape,heads", [((8, 1024, 1024), 16),
                                         ((1, 4096, 2048), 16)])
def test_the_op_and_its_gradient_lower_one_forward_kernel_for_tpu(shape,
                                                                  heads):
    """The `flash_attention` op and its gradient at the shapes of
    gpt2m-train and ouro-train-4k, lowered for the TPU as one program:
    the forward kernel once and the backward's one kernel, where the
    generic gradient holds the forward kernel a second time."""
    from paddle_tpu.ops import registry

    info = registry.get_op_info("flash_attention")
    attrs = {"num_heads": heads, "causal": True}
    x = jax.ShapeDtypeStruct(shape, jnp.bfloat16)

    def op_and(gradient):
        def step(q, k, v, dout):
            ins = {"Q": [q], "K": [k], "V": [v]}
            outs = info.kernel(None, ins, attrs)
            return outs["Out"], gradient(dict(
                ins, **{"O@Out": outs["Out"], "O@Lse": outs["Lse"],
                        "OG@Out": [dout]}))
        return jax.export.export(jax.jit(step), platforms=["tpu"])(
            x, x, x, x).mlir_module()

    explicit = op_and(lambda ins: info.grad_kernel(None, ins, attrs))
    generic = op_and(lambda ins: registry.run_generic_grad(
        None, "flash_attention", ins, attrs))
    forward = 'kernel_name = "flash_attention_fwd'
    assert (explicit.count(forward), generic.count(forward)) == (1, 2)
    assert explicit.count("tpu_custom_call") == 2


@pytest.mark.parametrize("shape,heads,window,kernels", [
    # smallthinker-train-16k-ep8's window layers: the forward walks its
    # keys, the backward is the one kernel that walks them with a ring
    # of dq^T
    ((1, 16384, 3584), 28, 4096, 2),
    # a head's queries fit: the one backward kernel, resident keys
    ((2, 2048, 1024), 16, 512, 2),
    # a window that is no multiple of a block, inside one block
    ((1, 1024, 512), 4, 100, 2)])
def test_the_window_kernels_lower_for_tpu(shape, heads, window, kernels):
    """The op and its gradient op with a `window`, lowered for the TPU
    as one program: Mosaic takes the lower edge's chunk loops, index
    maps and compares, and the kernels' names carry the window."""
    from paddle_tpu.ops import registry

    info = registry.get_op_info("flash_attention")
    attrs = {"num_heads": heads, "causal": True, "window": window}
    x = jax.ShapeDtypeStruct(shape, jnp.bfloat16)

    def step(q, k, v, dout):
        ins = {"Q": [q], "K": [k], "V": [v]}
        outs = info.kernel(None, ins, attrs)
        return outs["Out"], info.grad_kernel(None, dict(
            ins, **{"O@Out": outs["Out"], "O@Lse": outs["Lse"],
                    "OG@Out": [dout]}), attrs)

    module = jax.export.export(jax.jit(step), platforms=["tpu"])(
        x, x, x, x).mlir_module()
    assert module.count("tpu_custom_call") == kernels
    assert module.count("_w%d_h" % window) >= kernels
    assert 'kernel_name = "flash_attention_fwd' in module


@pytest.mark.parametrize("kernel,lhs,rhs", [
    ("fwd", (32768, 2048), (64, 2048, 1024)),
    ("fwd", (32768, 1024), (64, 1024, 2048)),
    ("dx", (32768, 1024), (64, 2048, 1024)),
    ("dx", (32768, 2048), (64, 1024, 2048)),
    ("dw", (32768, 2048), (32768, 1024)),
    ("dw", (32768, 1024), (32768, 2048)),
])
def test_grouped_products_lower_for_tpu(kernel, lhs, rhs):
    """The grouped-product kernels at the shapes of olmoe-train-4k (32768
    routed rows, 64 experts, 2048 <-> 1024, bfloat16), lowered for the
    TPU from this CPU host: one Mosaic kernel, named with the blocks it
    chose from the shapes."""
    from paddle_tpu.kernels import grouped_matmul

    fn = {"fwd": grouped_matmul.gmm, "dx": grouped_matmul.gmm_dx,
          "dw": grouped_matmul.gmm_dw}[kernel]
    module = jax.export.export(jax.jit(fn), platforms=["tpu"])(
        jax.ShapeDtypeStruct(lhs, jnp.bfloat16),
        jax.ShapeDtypeStruct(rhs, jnp.bfloat16),
        jax.ShapeDtypeStruct((64,), jnp.int32)).mlir_module()
    assert module.count("tpu_custom_call") == 1
    assert 'kernel_name = "moe_gmm_%s_m256_' % kernel in module


def test_the_expert_op_and_its_gradient_lower_nine_products_for_tpu():
    """`moe_experts` and its explicit gradient at the cell's shapes as
    one TPU program: the three forward products once, three dx and
    three dw, and no forward product a second time."""
    from paddle_tpu.ops import registry

    info = registry.get_op_info("moe_experts")
    n, k, e, d, f = 4096, 8, 64, 2048, 1024
    bf16 = jnp.bfloat16
    ins = {"X": [jax.ShapeDtypeStruct((1, n, d), bf16)],
           "TopW": [jax.ShapeDtypeStruct((n, k), jnp.float32)],
           "TopIdx": [jax.ShapeDtypeStruct((n, k), jnp.int32)],
           "WGate": [jax.ShapeDtypeStruct((e, d, f), jnp.float32)],
           "WUp": [jax.ShapeDtypeStruct((e, d, f), jnp.float32)],
           "WDown": [jax.ShapeDtypeStruct((e, f, d), jnp.float32)]}

    def step(ins, d_out):
        outs = info.kernel(None, ins, {})
        grad_ins = dict(ins, **{"OG@Out": [d_out]})
        grad_ins.update({"O@" + slot: v for slot, v in outs.items()})
        return outs["Out"], info.grad_kernel(None, grad_ins, {})

    with fluid.amp.bf16_guard():
        module = jax.export.export(jax.jit(step), platforms=["tpu"])(
            ins, ins["X"][0]).mlir_module()
    assert [module.count('kernel_name = "moe_gmm_%s_' % kernel)
            for kernel in ("fwd", "dx", "dw")] == [3, 3, 3]
    assert module.count("tpu_custom_call") == 9


def test_the_chunked_row_work_of_a_held_share_lowers_for_tpu():
    """`moe_experts` and its gradient at smallthinker-train-16k-ep8's
    shape (16384 tokens x 6, experts 24..31 of 64, 2560 -> 768, ReGLU,
    bfloat16 compute) as one TPU program: the row work between the
    grouped products as loops over chunks of 8192 of the 98304 rows,
    under `moe_compact` in each of the op's three scopes, forward and
    backward.  Every grouped product stands outside the loops, over
    whole arrays, and is lowered once: three forward, three dx, three
    dw.  No scope `moe_all_rows` is left (that the lowering holds no
    choice of a body on data: tests/test_moe_share_grad.py)."""
    from paddle_tpu.obs import telemetry
    from paddle_tpu.ops import registry

    info = registry.get_op_info("moe_experts")
    n, k, e, d, f = 16384, 6, 8, 2560, 768
    bf16 = jnp.bfloat16
    attrs = {"first_expert": 24, "scored": 64, "activation": "relu"}
    ins = {"X": [jax.ShapeDtypeStruct((1, n, d), bf16)],
           "TopW": [jax.ShapeDtypeStruct((n, k), jnp.float32)],
           "TopIdx": [jax.ShapeDtypeStruct((n, k), jnp.int32)],
           "WGate": [jax.ShapeDtypeStruct((e, d, f), jnp.float32)],
           "WUp": [jax.ShapeDtypeStruct((e, d, f), jnp.float32)],
           "WDown": [jax.ShapeDtypeStruct((e, f, d), jnp.float32)]}

    def step(ins, d_out):
        outs = info.kernel(None, ins, attrs)
        grad_ins = dict(ins, **{"OG@Out": [d_out]})
        grad_ins.update({"O@" + slot: v for slot, v in outs.items()})
        return outs["Out"], info.grad_kernel(None, grad_ins, attrs)

    before = telemetry.snapshot()
    with fluid.amp.bf16_guard():
        module = jax.export.export(jax.jit(step), platforms=["tpu"])(
            ins, ins["X"][0]).mlir_module()
    delta = telemetry.snapshot_delta(before)
    assert [module.count('kernel_name = "moe_gmm_%s_' % kernel)
            for kernel in ("fwd", "dx", "dw")] == [3, 3, 3]
    # and the arrays nothing has written that the loops start from: the
    # forward's h and rows' sums, the backward's rows of dOut and the
    # rows' parts of the routing weights' gradient
    assert module.count('kernel_name = "moe_unwritten"') == 4
    assert module.count("tpu_custom_call") == 9 + 4
    for phase in ("moe_route", "moe_experts", "moe_combine"):
        assert "/%s/moe_compact/while/" % phase in module, phase
    assert "moe_all_rows" not in module and "stablehlo.if" not in module
    assert delta[
        "moe_share_compact_lowerings_total{chunk=8192,rows=98304}"] == 1


def test_the_scan_kernels_lower_for_tpu():
    """The chunked state-space scan's two kernels at the shapes of
    granite-train-4k (1 x 4096 positions, 64 heads of 64, state 128,
    chunk 256, bfloat16 operands), lowered for the TPU from this CPU
    host: one Mosaic kernel each, named with its chunk and the heads a
    grid step takes."""
    import functools

    from paddle_tpu.kernels import ssd

    bf16, f32 = jnp.bfloat16, jnp.float32
    wide = jax.ShapeDtypeStruct((1, 4096, 4096), bf16)
    steps = jax.ShapeDtypeStruct((1, 4096, 64), f32)
    narrow = jax.ShapeDtypeStruct((1, 4096, 128), bf16)
    skip = jax.ShapeDtypeStruct((64,), f32)
    states = jax.ShapeDtypeStruct((1, 16, 128, 4096), f32)
    fwd = jax.export.export(
        jax.jit(functools.partial(ssd.fwd_kernels, chunk=256)),
        platforms=["tpu"])(wide, steps, steps, narrow, narrow,
                           skip).mlir_module()
    assert fwd.count("tpu_custom_call") == 1
    assert 'kernel_name = "ssd_fwd_c256_h2"' in fwd
    bwd = jax.export.export(
        jax.jit(functools.partial(ssd.bwd_kernels, chunk=256)),
        platforms=["tpu"])(wide, steps, steps, narrow, narrow, skip,
                           states, wide).mlir_module()
    assert bwd.count("tpu_custom_call") == 1
    assert 'kernel_name = "ssd_bwd_c256_h2"' in bwd


def test_the_scan_op_and_its_gradient_lower_one_kernel_each_for_tpu():
    """`ssd_scan` and its explicit gradient at the cell's shapes as one
    TPU program under bfloat16 compute: the forward kernel once, the
    backward kernel once, no forward kernel under the gradient."""
    from paddle_tpu.ops import registry

    info = registry.get_op_info("ssd_scan")
    bf16, f32 = jnp.bfloat16, jnp.float32
    attrs = {"num_heads": 64, "chunk_size": 256}
    ins = {"X": [jax.ShapeDtypeStruct((1, 4096, 4096), bf16)],
           "Dt": [jax.ShapeDtypeStruct((1, 4096, 64), bf16)],
           "B": [jax.ShapeDtypeStruct((1, 4096, 128), bf16)],
           "C": [jax.ShapeDtypeStruct((1, 4096, 128), bf16)]}
    ins.update({slot: [jax.ShapeDtypeStruct((64,), f32)]
                for slot in ("DtBias", "ALog", "D")})

    def step(ins, dy):
        outs = info.kernel(None, ins, attrs)
        grad_ins = dict(ins, **{"OG@Y": [dy]})
        grad_ins.update({"O@" + slot: v for slot, v in outs.items()})
        return outs["Y"], info.grad_kernel(None, grad_ins, attrs)

    with fluid.amp.bf16_guard():
        module = jax.export.export(jax.jit(step), platforms=["tpu"])(
            ins, ins["X"][0]).mlir_module()
    assert [module.count('kernel_name = "ssd_%s_c256_h2"' % kernel)
            for kernel in ("fwd", "bwd")] == [1, 1]
    assert module.count("tpu_custom_call") == 2


@pytest.mark.parametrize("length,kernels", [
    (1, ["ssd_step_r64_b4"]), (256, ["ssd_block_c256_h2"])])
def test_the_carried_scan_lowers_for_tpu(length, kernels):
    """`ssd_scan` with `State` at granite-decode-ep4's shapes (64 rows,
    128 heads of 64 over a state of 128, bfloat16 operands, a float32
    state of [rows, 128, 8192]) lowered for the TPU from this CPU host:
    a step is the step kernel over blocks of 4 rows (16 MiB of state a
    grid step, and the VMEM limit that follows from the block) whose
    state is its result's buffer; a prompt's block of one chunk holds
    the block kernel, which keeps no state a chunk."""
    from paddle_tpu.kernels import ssd_step
    from paddle_tpu.ops import registry

    info = registry.get_op_info("ssd_scan")
    bf16, f32 = jnp.bfloat16, jnp.float32
    rows, heads, dim, entries = 64, 128, 64, 128
    ins = {"X": [jax.ShapeDtypeStruct((rows, length, heads * dim), bf16)],
           "Dt": [jax.ShapeDtypeStruct((rows, length, heads), bf16)],
           "B": [jax.ShapeDtypeStruct((rows, length, entries), bf16)],
           "C": [jax.ShapeDtypeStruct((rows, length, entries), bf16)],
           "State": [jax.ShapeDtypeStruct((rows, entries, heads * dim),
                                          f32)]}
    ins.update({slot: [jax.ShapeDtypeStruct((heads,), f32)]
                for slot in ("DtBias", "ALog", "D")})

    def step(ins):
        return info.kernel(None, ins, {"num_heads": heads,
                                       "chunk_size": 256})

    exported = jax.export.export(jax.jit(step), platforms=["tpu"])(ins)
    module = exported.mlir_module()
    assert module.count("tpu_custom_call") == len(kernels)
    for kernel in kernels:
        assert 'kernel_name = "%s"' % kernel in module
    assert sorted(tuple(a.shape) for a in exported.out_avals) == sorted([
        (rows, length, heads * dim), (rows, entries, heads * dim)])
    if length == 1:     # the state's buffer is the new state's
        assert "output_tuple_indices = [1], operand_index = 4" in module
        assert _vmem_limit_stated(module) \
            == ssd_step.vmem_limit(4, entries, heads * dim)


def test_the_latent_decode_kernel_lowers_for_tpu():
    """`mla_cached_attention` at pangu-decode-ep16's shapes (256 rows,
    128 heads, a 1024-slot bfloat16 cache of 512 + 64 values) lowered
    for the TPU from this CPU host: one Mosaic kernel, named with the
    block of slots chosen from the shapes; with a chosen set of 256 the
    same kernel over the gathered rows, in one block of 256 (PR 70),
    and the plain products where the set is no multiple of 128."""
    from paddle_tpu.ops import registry

    kernel = registry.get_op_info("mla_cached_attention").kernel
    b, h, t, c, r, d = 256, 128, 1024, 512, 64, 128
    bf16 = jnp.bfloat16
    ins = {"QNope": [jax.ShapeDtypeStruct((b, 1, h * d), bf16)],
           "QRope": [jax.ShapeDtypeStruct((b, 1, h * r), bf16)],
           "CNew": [jax.ShapeDtypeStruct((b, 1, c), bf16)],
           "RNew": [jax.ShapeDtypeStruct((b, 1, r), bf16)],
           "Cache": [jax.ShapeDtypeStruct((b, t, c + r), bf16)],
           "WUk": [jax.ShapeDtypeStruct((c, h * d), bf16)],
           "WUv": [jax.ShapeDtypeStruct((c, h * d), bf16)],
           "Position": [jax.ShapeDtypeStruct((b,), jnp.int32)]}

    def step(ins):
        return kernel(None, ins, {"num_heads": h})

    module = jax.export.export(jax.jit(step), platforms=["tpu"])(
        ins).mlir_module()
    assert module.count("tpu_custom_call") == 1
    assert 'kernel_name = "mla_decode_k512"' in module
    for top_k, name in ((256, "mla_decode_k256"), (200, None)):
        chosen = dict(
            ins, Selected=[jax.ShapeDtypeStruct((b, top_k), jnp.int32)],
            Live=[jax.ShapeDtypeStruct((b,), jnp.int32)])
        module = jax.export.export(
            jax.jit(step), platforms=["tpu"])(chosen).mlir_module()
        assert module.count("tpu_custom_call") == (name is not None)
        assert name is None or 'kernel_name = "%s"' % name in module
    # a prefill application of the cell: 16 positions a row, one kernel
    # named with them
    block = {k: [jax.ShapeDtypeStruct((b, 16) + v[0].shape[2:], bf16)]
             if k in ("QNope", "QRope", "CNew", "RNew") else v
             for k, v in ins.items()}
    module = jax.export.export(jax.jit(step), platforms=["tpu"])(
        block).mlir_module()
    assert module.count("tpu_custom_call") == 1
    assert 'kernel_name = "mla_decode_k256_t16"' in module


@pytest.mark.parametrize("block,name", [(1, "mla_decode_k512"),
                                        (64, "mla_decode_k512_t64")])
def test_the_latent_decode_kernel_takes_32_heads(block, name):
    """`mla_cached_attention` at ling3-decode-ep16's shapes (128 rows,
    32 heads with a full-rank query, a 1024-slot bfloat16 cache of 512 +
    64 values) lowered for the TPU: both forms of the kernel take the
    shape they were not sized at (a step: 4 rows of 32 heads a grid
    step; a block of 64 positions: all 32 heads, 512 query rows, over
    blocks of 512 slots)."""
    from paddle_tpu.ops import registry

    kernel = registry.get_op_info("mla_cached_attention").kernel
    b, h, t, c, r, d = 128, 32, 1024, 512, 64, 128
    bf16 = jnp.bfloat16
    ins = {"QNope": [jax.ShapeDtypeStruct((b, block, h * d), bf16)],
           "QRope": [jax.ShapeDtypeStruct((b, block, h * r), bf16)],
           "CNew": [jax.ShapeDtypeStruct((b, block, c), bf16)],
           "RNew": [jax.ShapeDtypeStruct((b, block, r), bf16)],
           "Cache": [jax.ShapeDtypeStruct((b, t, c + r), bf16)],
           "WUk": [jax.ShapeDtypeStruct((c, h * d), bf16)],
           "WUv": [jax.ShapeDtypeStruct((c, h * d), bf16)],
           "Position": [jax.ShapeDtypeStruct((b,), jnp.int32)]}
    module = jax.export.export(
        jax.jit(lambda ins: kernel(None, ins, {"num_heads": h})),
        platforms=["tpu"])(ins).mlir_module()
    assert module.count("tpu_custom_call") == 1
    assert 'kernel_name = "%s"' % name in module


@pytest.mark.parametrize("block,kernel_name", [
    (4, "gqa_decode_k1024_t4_b4"), (128, "gqa_decode_k1024_t128_b4"),
    (8, "gqa_decode_k1024_t8_b4")])
def test_the_block_causal_walk_lowers_for_tpu(block, kernel_name):
    """`cached_attention` under `diffusion_block` 4 at sdar-diffuse-pp8's
    shapes (128 rows, 32 query heads over 4 key/value heads of 128, a
    1024-slot bfloat16 cache) lowered for the TPU from this CPU host: a
    pass over one block of 4, a prefill block of 128 positions and a
    block's first pass over 8 (the block before's commit and its own)
    all walk the live slots, and the kernel's name says the mask's
    block."""
    from paddle_tpu.ops import registry

    kernel = registry.get_op_info("cached_attention").kernel
    b, h, kv, d, bf16 = 128, 32, 4, 128, jnp.bfloat16
    cache = jax.ShapeDtypeStruct((b, kv, 1024, d), bf16)
    ins = {"Q": [jax.ShapeDtypeStruct((b, block, h * d), bf16)],
           "KNew": [jax.ShapeDtypeStruct((b, block, kv * d), bf16)],
           "VNew": [jax.ShapeDtypeStruct((b, block, kv * d), bf16)],
           "KCache": [cache], "VCache": [cache],
           "Position": [jax.ShapeDtypeStruct((b,), jnp.int32)]}

    def step(ins):
        return kernel(None, ins, {"num_heads": h, "num_kv_heads": kv,
                                  "diffusion_block": 4})

    module = jax.export.export(jax.jit(step), platforms=["tpu"])(
        ins).mlir_module()
    assert module.count("tpu_custom_call") == 1
    assert 'kernel_name = "%s"' % kernel_name in module


@pytest.mark.parametrize("block,window,kernels", [
    (1, 0, ["gqa_decode_k2048"]), (1, 128, ["gqa_decode_w128_h8"]),
    (128, 0, ["gqa_decode_k1024_t128"]), (128, 128, [])])
def test_the_grouped_decode_kernel_lowers_for_tpu(block, window, kernels):
    """`cached_attention` at exaone-turn-32k-ep16's shapes (8 rows, 64
    query heads over 8 key/value heads of 128, a 32,768-slot cache or a
    128-slot ring, bfloat16) lowered for the TPU from this CPU host: a
    decode step walks the live slots of either cache, a block of 128
    positions those of the whole extent (the kernel's name says the
    block of slots and the positions; a ring's, whose 128 slots of a
    head are 32 KB, that a grid step takes a row's eight heads), and a
    block through a ring holds no kernel."""
    from paddle_tpu.ops import registry

    kernel = registry.get_op_info("cached_attention").kernel
    b, h, kv, d, bf16 = 8, 64, 8, 128, jnp.bfloat16
    cache = jax.ShapeDtypeStruct((b, kv, window or 32768, d), bf16)
    ins = {"Q": [jax.ShapeDtypeStruct((b, block, h * d), bf16)],
           "KNew": [jax.ShapeDtypeStruct((b, block, kv * d), bf16)],
           "VNew": [jax.ShapeDtypeStruct((b, block, kv * d), bf16)],
           "KCache": [cache], "VCache": [cache],
           "Position": [jax.ShapeDtypeStruct((b,), jnp.int32)]}

    def step(ins):
        return kernel(None, ins, {"num_heads": h, "num_kv_heads": kv,
                                  "window": window})

    module = jax.export.export(jax.jit(step), platforms=["tpu"])(
        ins).mlir_module()
    assert module.count("tpu_custom_call") == len(kernels)
    for name in kernels:
        assert 'kernel_name = "%s"' % name in module


@pytest.mark.parametrize("block,window,readonly,kernels", [
    (1, 0, False, ["gqa_decode_k2048"]),
    (1, 512, False, ["gqa_decode_w512_h10"]),
    (128, 0, False, ["gqa_decode_k2048_t128"]), (128, 512, False, []),
    (1, 0, True, ["gqa_decode_k2048"])])
def test_pairs_of_64_wide_heads_lower_the_grouped_kernel_for_tpu(
        block, window, readonly, kernels):
    """`cached_attention` at phi4flash-turn-16k's shapes (16 rows, 40
    query heads over 10 pairs of key/value heads kept side by side as
    128-wide heads, a 16,384-slot cache or a 512-slot ring, bfloat16)
    lowered for the TPU from this CPU host: a step walks the live slots
    of either cache (a grid step of a ring's walk takes a row's ten
    heads, 128 KB each), a block of 128 positions those of the whole extent,
    a block through a ring holds no kernel; and the form without KNew /
    VNew walks the same kernel and writes no slot."""
    from paddle_tpu.ops import registry

    kernel = registry.get_op_info("cached_attention").kernel
    b, h, kv, d, bf16 = 16, 40, 10, 128, jnp.bfloat16
    cache = jax.ShapeDtypeStruct((b, kv, window or 16384, d), bf16)
    ins = {"Q": [jax.ShapeDtypeStruct((b, block, h * d), bf16)],
           "KCache": [cache], "VCache": [cache],
           "Position": [jax.ShapeDtypeStruct((b,), jnp.int32)]}
    if not readonly:
        new = jax.ShapeDtypeStruct((b, block, kv * d), bf16)
        ins.update(KNew=[new], VNew=[new])

    def step(ins):
        return kernel(None, ins, {"num_heads": h, "num_kv_heads": kv,
                                  "window": window, "sm_scale": 0.125})

    exported = jax.export.export(jax.jit(step), platforms=["tpu"])(ins)
    module = exported.mlir_module()
    assert module.count("tpu_custom_call") == len(kernels)
    for name in kernels:
        assert 'kernel_name = "%s"' % name in module
    if readonly:
        assert len(exported.out_avals) == 1
        assert "dynamic_update_slice" not in module


def test_the_selective_scan_lowers_for_tpu_without_a_kernel():
    """`selective_scan` at phi4flash-turn-16k's shapes (16 rows, 5120
    channels, 16 state entries, a float32 state [16, 16, 5120]) lowered
    for the TPU from this CPU host: a step is plain float32 arithmetic
    over the state, a block of 128 positions one `while` over that same
    update; no Mosaic kernel in either, and the state keeps its type."""
    from paddle_tpu.ops import registry

    kernel = registry.get_op_info("selective_scan").kernel
    b, d, n, bf16, f32 = 16, 5120, 16, jnp.bfloat16, jnp.float32
    for block in (1, 128):
        ins = {"X": [jax.ShapeDtypeStruct((b, block, d), bf16)],
               "Dt": [jax.ShapeDtypeStruct((b, block, d), f32)],
               "DtBias": [jax.ShapeDtypeStruct((d,), f32)],
               "ALog": [jax.ShapeDtypeStruct((d, n), f32)],
               "B": [jax.ShapeDtypeStruct((b, block, n), f32)],
               "C": [jax.ShapeDtypeStruct((b, block, n), f32)],
               "D": [jax.ShapeDtypeStruct((d,), f32)],
               "State": [jax.ShapeDtypeStruct((b, n, d), f32)]}
        exported = jax.export.export(
            jax.jit(lambda ins: kernel(None, ins, {})), platforms=["tpu"])(ins)
        module = exported.mlir_module()
        assert "tpu_custom_call" not in module
        assert ("stablehlo.while" in module) == (block > 1)
        assert [str(a.dtype) for a in exported.out_avals] \
            == ["bfloat16", "float32"]
        assert exported.out_avals[1].shape == (b, n, d)


def test_the_chosen_sets_decode_kernel_lowers_for_tpu():
    """`cached_attention` with `Selected` at keye-turn-64k-ep8's shape (8
    rows, 32 query heads over 4 key/value heads of 128, 65,536-slot
    bfloat16 caches, 2048 chosen) lowered for the TPU from this CPU
    host: one Mosaic kernel, named with the set and the entries a grid
    step folds, over the two gathers of whole slots."""
    from paddle_tpu.ops import registry

    kernel = registry.get_op_info("cached_attention").kernel
    b, h, kv, d, bf16 = 8, 32, 4, 128, jnp.bfloat16
    cache = jax.ShapeDtypeStruct((b, kv, 65536, d), bf16)
    ins = {"Q": [jax.ShapeDtypeStruct((b, 1, h * d), bf16)],
           "KNew": [jax.ShapeDtypeStruct((b, 1, kv * d), bf16)],
           "VNew": [jax.ShapeDtypeStruct((b, 1, kv * d), bf16)],
           "KCache": [cache], "VCache": [cache],
           "Position": [jax.ShapeDtypeStruct((b,), jnp.int32)],
           "Selected": [jax.ShapeDtypeStruct((b, 2048), jnp.int32)],
           "Live": [jax.ShapeDtypeStruct((b,), jnp.int32)]}

    def step(ins):
        return kernel(None, ins, {"num_heads": h, "num_kv_heads": kv})

    module = jax.export.export(jax.jit(step), platforms=["tpu"])(
        ins).mlir_module()
    assert module.count("tpu_custom_call") == 1
    assert 'kernel_name = "gqa_decode_sel2048_c2048"' in module
    assert module.count("stablehlo.gather") == 2
    # whole slots, their heads side by side
    assert "tensor<8x2048x4x128xbf16>" in module


def test_a_block_of_chosen_sets_lowers_for_tpu():
    """`cached_attention` with `Selected` [batch, T, top_k] at
    keye-turn-64k-ep8's shape, the 64 positions an application of its
    prefill takes: the block is written, then a loop over tiles of
    positions holds one gather of whole slots a cache and the step's own
    Mosaic kernel over batch x tile rows, a `Live` a row."""
    from paddle_tpu.ops import attention, registry

    kernel = registry.get_op_info("cached_attention").kernel
    b, t, h, kv, d, bf16 = 8, 64, 32, 4, 128, jnp.bfloat16
    tile = attention._tile_positions(t, b * 2048 * 2 * kv * d * 2,
                                     attention._CHOSEN_TILE_BYTES)
    assert tile == 2
    cache = jax.ShapeDtypeStruct((b, kv, 65536, d), bf16)
    ins = {"Q": [jax.ShapeDtypeStruct((b, t, h * d), bf16)],
           "KNew": [jax.ShapeDtypeStruct((b, t, kv * d), bf16)],
           "VNew": [jax.ShapeDtypeStruct((b, t, kv * d), bf16)],
           "KCache": [cache], "VCache": [cache],
           "Position": [jax.ShapeDtypeStruct((b,), jnp.int32)],
           "Selected": [jax.ShapeDtypeStruct((b, t, 2048), jnp.int32)],
           "Live": [jax.ShapeDtypeStruct((b, t), jnp.int32)]}

    def block(ins):
        return kernel(None, ins, {"num_heads": h, "num_kv_heads": kv,
                                  "prefill_block": t})

    exported = jax.export.export(jax.jit(block), platforms=["tpu"])(ins)
    module = exported.mlir_module()
    assert module.count("tpu_custom_call") == 1
    assert 'kernel_name = "gqa_decode_sel2048_c2048"' in module
    assert "stablehlo.while" in module
    assert module.count("stablehlo.gather") == 2
    # a tile's whole slots, a position a row of the kernel
    assert "tensor<%dx2048x4x128xbf16>" % (b * tile) in module
    assert "tensor<%dx%dx4x128xbf16>" % (b, t * 2048) not in module
    assert exported.out_avals[1].shape == (b, t, h * d)


@pytest.mark.parametrize("rows,slots,heads,dim,name", [
    (8, 65536, 16, 64, "topk_select_s65536_k2048"),
    (16, 16384, 64, 128, "topk_select_s16384_k2048"),
    (8, 32768, 32, 128, "topk_select_s32768_k2048")])
def test_the_choosers_selection_lowers_one_kernel_for_tpu(rows, slots, heads,
                                                          dim, name):
    """`mla_index_select` at keye-turn-64k-ep8's, dsv32-turn-16k-ep16's
    and hy4-turn-32k-ep16's
    shapes (2048 of 65,536, 16,384 or 32,768 bfloat16 index keys a row) lowered
    for the TPU from this CPU host: the selection is one Mosaic kernel
    named with the extent and the slots chosen, and the module holds no
    sort and no top-k."""
    from paddle_tpu.ops import registry

    kernel = registry.get_op_info("mla_index_select").kernel
    bf16 = jnp.bfloat16
    ins = {"Q": [jax.ShapeDtypeStruct((rows, 1, heads * dim), bf16)],
           "W": [jax.ShapeDtypeStruct((rows, 1, heads), bf16)],
           "KNew": [jax.ShapeDtypeStruct((rows, 1, dim), bf16)],
           "Cache": [jax.ShapeDtypeStruct((rows, slots, dim), bf16)],
           "Position": [jax.ShapeDtypeStruct((rows,), jnp.int32)]}

    def step(ins):
        return kernel(None, ins, {"num_heads": heads, "top_k": 2048})

    module = jax.export.export(jax.jit(step), platforms=["tpu"])(
        ins).mlir_module()
    assert module.count("tpu_custom_call") == 1
    assert 'kernel_name = "%s"' % name in module
    assert "stablehlo.sort" not in module and "top_k" not in module


@pytest.mark.parametrize("rows,block,slots,heads,dim,tile,name", [
    (16, 32, 16384, 64, 128, 4, "topk_select_s16384_k2048"),
    (8, 64, 32768, 32, 128, 8, "topk_select_s32768_k2048")])
def test_a_block_of_the_choosers_positions_lowers_for_tpu(rows, block, slots,
                                                          heads, dim, tile,
                                                          name):
    """`mla_index_select` over a block of a question's positions at
    dsv32-turn-16k-ep16's and hy4-turn-32k-ep16's shapes (the blocks
    their steps state: 32 positions of 16 rows, 64 of 8) lowered for the
    TPU from this CPU host: still the one selection kernel, over rows x
    block rows of scores; the heads' scores are a tile of positions
    inside a loop and never the whole block's; no sort."""
    from paddle_tpu.models.latent_moe_program import prefill_block
    from paddle_tpu.ops import registry

    assert prefill_block(rows, 128 if rows == 16 else 64, 512, 64,
                         (heads, dim, 2048), slots) == block
    kernel = registry.get_op_info("mla_index_select").kernel
    bf16 = jnp.bfloat16
    ins = {"Q": [jax.ShapeDtypeStruct((rows, block, heads * dim), bf16)],
           "W": [jax.ShapeDtypeStruct((rows, block, heads), bf16)],
           "KNew": [jax.ShapeDtypeStruct((rows, block, dim), bf16)],
           "Cache": [jax.ShapeDtypeStruct((rows, slots, dim), bf16)],
           "Position": [jax.ShapeDtypeStruct((rows,), jnp.int32)]}

    def step(ins):
        return kernel(None, ins, {"num_heads": heads, "top_k": 2048})

    exported = jax.export.export(jax.jit(step), platforms=["tpu"])(ins)
    assert [tuple(a.shape) for a in exported.out_avals] == [
        (rows, slots, dim), (rows, block), (rows, block, 2048)]
    module = exported.mlir_module()
    assert module.count("tpu_custom_call") == 1
    assert 'kernel_name = "%s"' % name in module
    assert "stablehlo.sort" not in module and "top_k" not in module
    assert "stablehlo.while" in module
    assert "tensor<%dx%dx%dx%dxf32>" % (rows, tile, heads, slots) in module
    assert "tensor<%dx%dx%dx%dxf32>" % (rows, block, heads, slots) \
        not in module
    assert "tensor<%dx%dxf32>" % (rows * block, slots) in module


def test_the_sink_and_the_streams_lower_for_tpu():
    """hy4-turn-32k-ep16's step at its shapes (8 rows, 64 heads of 192 +
    64 over 512 latents, 2048 chosen of 32,768 bfloat16 slots, a sink a
    head; four streams of 6144) lowered for the TPU from this CPU host:
    the chosen-set step with `Sink` is one gather read by one Mosaic
    kernel, the walk that takes the sink (PR 70; no float32 score of
    the 2048 entries is in the module), and the hyper-connection's
    three ops are plain float32 arithmetic under the scope
    `hyper_connection`, twenty Sinkhorn iterations unrolled, without a
    kernel."""
    from paddle_tpu.ops import registry

    b, h, bf16, f32 = 8, 64, jnp.bfloat16, jnp.float32
    attend = registry.get_op_info("mla_cached_attention").kernel
    ins = {"QNope": [jax.ShapeDtypeStruct((b, 1, h * 192), bf16)],
           "QRope": [jax.ShapeDtypeStruct((b, 1, h * 64), bf16)],
           "CNew": [jax.ShapeDtypeStruct((b, 1, 512), bf16)],
           "RNew": [jax.ShapeDtypeStruct((b, 1, 64), bf16)],
           "Cache": [jax.ShapeDtypeStruct((b, 32768, 576), bf16)],
           "WUk": [jax.ShapeDtypeStruct((512, h * 192), bf16)],
           "WUv": [jax.ShapeDtypeStruct((512, h * 256), bf16)],
           "Position": [jax.ShapeDtypeStruct((b,), jnp.int32)],
           "Selected": [jax.ShapeDtypeStruct((b, 2048), jnp.int32)],
           "Live": [jax.ShapeDtypeStruct((b,), jnp.int32)],
           "Sink": [jax.ShapeDtypeStruct((h,), f32)]}
    module = jax.export.export(
        jax.jit(lambda ins: attend(None, ins, {"num_heads": h})),
        platforms=["tpu"])(ins).mlir_module()
    assert module.count("tpu_custom_call") == 1
    assert 'kernel_name = "mla_decode_k2048"' in module
    assert module.count('"stablehlo.gather"') == 1
    assert "tensor<8x2048x576xbf16>" in module
    assert "tensor<8x64x2048xf32>" not in module

    maps = registry.get_op_info("hc_maps").kernel
    pre = registry.get_op_info("hc_pre").kernel
    post = registry.get_op_info("hc_post").kernel
    streams = jax.ShapeDtypeStruct((b, 1, 4, 6144), bf16)

    def mixed(x, p, a, bias, y):
        m = maps(None, {"X": [x], "P": [p], "Alpha": [a], "Bias": [bias]},
                 {"epsilon": 1e-6, "magnitude": 2.0, "iterations": 20})
        u = pre(None, {"X": [x], "Pre": m["Pre"]}, {})["U"][0]
        out = post(None, {"X": [x], "Res": m["Res"], "Post": m["Post"],
                          "Y": [y]}, {})["XOut"][0]
        return u, out

    exported = jax.export.export(jax.jit(mixed), platforms=["tpu"])(
        streams, jax.ShapeDtypeStruct((4 * 6144, 24), f32),
        jax.ShapeDtypeStruct((3,), f32), jax.ShapeDtypeStruct((24,), f32),
        jax.ShapeDtypeStruct((b, 1, 6144), bf16))
    module = exported.mlir_module()
    assert "tpu_custom_call" not in module
    assert [str(a.dtype) for a in exported.out_avals] == ["bfloat16"] * 2
    assert exported.out_avals[1].shape == (b, 1, 4, 6144)
    # rows over their sums, columns over theirs, twenty times
    assert module.count("stablehlo.divide") >= 40
    assert "tensor<8x1x24576xf32>" in module


@pytest.mark.parametrize("block,kernels", [
    (1, ["gqa_write_r4", "gqa_decode_k512_h16"]), (128, [])])
def test_the_narrow_decode_kernels_lower_for_tpu(block, kernels):
    """`cached_attention` at gpt2m-decode's shape (48 rows, 16 heads of
    64, 1024-slot bfloat16 caches) lowered for the TPU from this CPU
    host: a decode step holds two Mosaic kernels, the slot's write and
    the walk (named with its block of slots and the heads a grid step),
    and no loop of the op's own (`decode_hbm_roofline` asks for exactly
    two outermost `while`s a call); a prefill block of 128 positions
    holds none."""
    from paddle_tpu.ops import registry

    kernel = registry.get_op_info("cached_attention").kernel
    b, h, d, bf16 = 48, 16, 64, jnp.bfloat16
    cache = jax.ShapeDtypeStruct((b, h, 1024, d), bf16)
    new = jax.ShapeDtypeStruct((b, block, h * d), bf16)
    ins = {"Q": [new], "KNew": [new], "VNew": [new], "KCache": [cache],
           "VCache": [cache],
           "Position": [jax.ShapeDtypeStruct((b,), jnp.int32)]}

    def step(ins):
        return kernel(None, ins, {"num_heads": h})

    module = jax.export.export(jax.jit(step), platforms=["tpu"])(
        ins).mlir_module()
    assert module.count("tpu_custom_call") == len(kernels)
    for name in kernels:
        assert 'kernel_name = "%s"' % name in module
    assert "stablehlo.while" not in module


def test_flash_attention_refuses_a_ragged_block():
    """A sequence its block does not divide raises with the shape in the
    message; the block no longer shrinks toward 1 without saying so."""
    x = jnp.zeros((1, 2, 200, 16), jnp.float32)
    with pytest.raises(ValueError, match=r"200.*\(1, 2, 200, 16\)"):
        flash_attention(x, x, x, None, False, 128, 128)


# -- no fallback that hides the device -------------------------------------

def test_chip_smoke_fails_fast_without_a_tpu():
    t0 = time.time()
    proc = _run(["chip_smoke.py"], {"JAX_PLATFORMS": "cpu"}, timeout=120)
    assert proc.returncode != 0
    assert time.time() - t0 < 60
    assert "platform=cpu" in proc.stdout
    # it stopped before the cache, before any model and before any result
    assert "compile cache" not in proc.stdout
    assert "phase" not in proc.stdout
    assert not _json_lines(proc.stdout)


def test_chip_smoke_step_agrees_with_the_executor_and_stays_put():
    """chip_smoke's own builder and step at a toy size: the jitted
    FunctionalProgram step with donated state gives the loss
    `Executor.run` gives on the same feeds, and leaves every state
    array on the device it was put on."""
    import chip_smoke

    main, startup, _, loss = chip_smoke.build_image_model(
        "lenet5", 4, 28, 10)
    feeds = chip_smoke.image_feeds(4, 28, 10, channels=1)
    scope = fluid.Scope()
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(startup, scope=scope)

    # not the default device: an array that strayed would show
    dev = jax.devices()[-1]
    step, state = chip_smoke.functional_step(
        main, ["image", "label"], loss.name, scope, dev)
    dev_feeds = jax.device_put(feeds, dev)
    got = []
    for _ in range(3):
        (fetch,), state = step(state, dev_feeds)
        got.append(chip_smoke.scalar(fetch))
    chip_smoke.check_on("functional state", state.values(), {dev})

    want = [chip_smoke.scalar(exe.run(main, feed=feeds, fetch_list=[loss],
                                      scope=scope)[0]) for _ in range(3)]
    assert got == pytest.approx(want, rel=1e-5)
    assert got[-1] < got[0]


def test_engine_without_a_place_sits_on_the_default_device():
    from paddle_tpu.obs.load import build_tiny_engine

    engine = build_tiny_engine()
    assert engine.place == fluid.Executor().place
    assert engine.param_devices() == {jax.devices()[0]}


def test_v2_placement_follows_the_backend_unless_told_not_to():
    from paddle_tpu.v2 import config

    saved = dict(config._state)
    try:
        config._state["use_tpu"] = None
        assert config._place() == fluid.TPUPlace(0)
        config.init(use_gpu=False)
        assert config._place() == fluid.CPUPlace()
        config.init(use_tpu=True)
        assert config._place() == fluid.TPUPlace(0)
    finally:
        config._state.update(saved)


def test_mesh_larger_than_the_platform_is_an_error():
    from paddle_tpu.parallel import make_mesh

    with pytest.raises(ValueError, match="platform has"):
        make_mesh(n_devices=len(jax.devices()) + 1)


def test_place_out_of_range_is_an_error():
    with pytest.raises(ValueError, match=r"TPUPlace\(99\)"):
        fluid.TPUPlace(99).device()
    assert fluid.TPUPlace(0).device() == jax.devices()[0]


# -- one place for the compile cache ---------------------------------------

_CACHE_PROBE = ("from paddle_tpu.utils.compile_cache import "
                "enable_compile_cache; import jax; "
                "print(enable_compile_cache()); "
                "print(jax.config.jax_compilation_cache_dir)")


def test_cache_dir_is_fixed_inside_the_checkout():
    """Unset, the cache goes to <checkout>/.jax_cache: the same string
    from two processes started in different directories."""
    outs = [subprocess.run(
        [sys.executable, "-c", _CACHE_PROBE], cwd=cwd, text=True,
        capture_output=True, timeout=120, check=True,
        env={**{k: v for k, v in os.environ.items()
                if k != "JAX_COMPILATION_CACHE_DIR"},
             "PYTHONPATH": REPO}).stdout.split()
        for cwd in (REPO, os.path.join(REPO, "tests"))]
    want = os.path.join(REPO, ".jax_cache")
    assert outs == [[want, want], [want, want]]


def test_cache_dir_follows_the_environment(tmp_path):
    proc = _run(["-c", _CACHE_PROBE],
                {"JAX_COMPILATION_CACHE_DIR": str(tmp_path)})
    assert proc.stdout.split() == [str(tmp_path), str(tmp_path)]


@pytest.mark.parametrize("ambient, want", [(None, "0"), ("2.5", "2.5")])
def test_compile_cache_min_compile_time(tmp_path, ambient, want):
    """Unset, every compile is kept (0 s: a warm start redoes none);
    set by the environment, the threshold is the environment's."""
    name = "JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"
    env = {"JAX_COMPILATION_CACHE_DIR": str(tmp_path)}
    if ambient is not None:
        env[name] = ambient
    proc = _run(["-c", _CACHE_PROBE.replace(
        "jax_compilation_cache_dir",
        "jax_persistent_cache_min_compile_time_secs")], env,
        env_drop=(name,))
    assert proc.stdout.split() == [str(tmp_path), want]


def test_save_after_amp_startup_round_trips(tmp_path):
    """A startup program run under AMP leaves bf16 parameters; numpy's
    formats cannot name bf16, so the export holds f32 and loads back
    (it used to come back as raw `|V2` and fail in device_put)."""
    from paddle_tpu.core.scope import Scope
    from paddle_tpu.serving import InferenceEngine

    fluid.amp.enable_bf16()
    try:
        main, startup = fluid.Program(), fluid.Program()
        with fluid.program_guard(main, startup):
            x = fluid.layers.data(name="x", shape=[8], dtype="float32")
            y = fluid.layers.fc(input=x, size=4, act="softmax")
        exe = fluid.Executor()
        with fluid.scope_guard(Scope()):
            exe.run(startup)
            fluid.io.save_inference_model(str(tmp_path), ["x"], [y], exe,
                                          main_program=main)
        engine = InferenceEngine.from_saved_model(str(tmp_path))
        (out,) = engine.run({"x": jnp.ones((2, 8), jnp.float32)})
        assert abs(float(out.sum()) - 2.0) < 2e-2
    finally:
        fluid.amp.disable_bf16()


@pytest.mark.parametrize("block,kernels", [
    (1, ["gqa_decode_k1024_d256"]), (128, ["gqa_decode_k1024_t128_d256"])])
def test_the_wide_head_decode_kernel_lowers_for_tpu(block, kernels):
    """`cached_attention` at qwen3next-decode-ep16's shape (128 rows, 16
    query heads over 2 key/value heads of 256, 1024-slot bfloat16
    caches) lowered for the TPU from this CPU host: a step and a
    prefill block of 128 positions both walk the live slots, two lane
    blocks a head (the kernel's name says the head's width)."""
    from paddle_tpu.ops import registry

    kernel = registry.get_op_info("cached_attention").kernel
    b, h, kv, d, bf16 = 128, 16, 2, 256, jnp.bfloat16
    cache = jax.ShapeDtypeStruct((b, kv, 1024, d), bf16)
    ins = {"Q": [jax.ShapeDtypeStruct((b, block, h * d), bf16)],
           "KNew": [jax.ShapeDtypeStruct((b, block, kv * d), bf16)],
           "VNew": [jax.ShapeDtypeStruct((b, block, kv * d), bf16)],
           "KCache": [cache], "VCache": [cache],
           "Position": [jax.ShapeDtypeStruct((b,), jnp.int32)]}

    def step(ins):
        return kernel(None, ins, {"num_heads": h, "num_kv_heads": kv})

    module = jax.export.export(jax.jit(step), platforms=["tpu"])(
        ins).mlir_module()
    assert module.count("tpu_custom_call") == len(kernels)
    for name in kernels:
        assert 'kernel_name = "%s"' % name in module


@pytest.mark.parametrize("block,kernel_name", [
    (1, "gqa_decode_k512_h10"), (128, "gqa_decode_k512_t128")])
def test_ungrouped_wide_heads_share_a_grid_step_for_tpu(block, kernel_name):
    """`cached_attention` at olmohybrid-decode-pp4's shape (128 rows, 30
    ungrouped heads of 128, 512-slot bfloat16 caches) lowered for the TPU
    from this CPU host: one head's 512 slots are 128 KB of each cache, no
    grid step's worth, so a decode step's walk takes ten heads a grid
    step (384 steps a layer, not 3,840) and its name says so; a prefill
    block of 128 positions is products, a head a step as before."""
    from paddle_tpu.ops import registry

    kernel = registry.get_op_info("cached_attention").kernel
    b, h, d, bf16 = 128, 30, 128, jnp.bfloat16
    cache = jax.ShapeDtypeStruct((b, h, 512, d), bf16)
    new = jax.ShapeDtypeStruct((b, block, h * d), bf16)
    ins = {"Q": [new], "KNew": [new], "VNew": [new],
           "KCache": [cache], "VCache": [cache],
           "Position": [jax.ShapeDtypeStruct((b,), jnp.int32)]}
    module = jax.export.export(jax.jit(
        lambda ins: kernel(None, ins, {"num_heads": h})),
        platforms=["tpu"])(ins).mlir_module()
    assert module.count("tpu_custom_call") == 1
    assert 'kernel_name = "%s"' % kernel_name in module


def _vmem_limit_stated(module):
    """The scoped VMEM a module's one Mosaic call asks for, bytes."""
    # the backend's configuration is a quoted string: \22 is its quote
    sizes = re.findall(r'scoped_memory_configs.{0,80}?size\\22: (\d+)',
                       module)
    assert len(sizes) == 1, sizes
    return int(sizes[0])


@pytest.mark.parametrize("length,kernels", [(1, ["gdn_step_r128_h32_b4"]),
                                            (128, [])])
def test_the_delta_rule_step_kernel_lowers_for_tpu(length, kernels):
    """`gated_delta_rule` at qwen3next-decode-ep16's shape (128 rows, 16
    key / 32 value heads of 128, a float32 state) lowered for the TPU
    from this CPU host: a step holds one Mosaic kernel over blocks of 4
    rows' 32 value heads (8 MiB of state a grid step, and the VMEM limit
    that follows from the block) whose state is its result's buffer, a
    block of 128 positions holds none (the chunked form is plain
    products)."""
    from paddle_tpu.kernels import gdn_step
    from paddle_tpu.ops import registry

    kernel = registry.get_op_info("gated_delta_rule").kernel
    b, hk, hv, d = 128, 16, 32, 128
    f32, bf16 = jnp.float32, jnp.bfloat16
    ins = {"Q": [jax.ShapeDtypeStruct((b, length, hk * d), bf16)],
           "K": [jax.ShapeDtypeStruct((b, length, hk * d), bf16)],
           "V": [jax.ShapeDtypeStruct((b, length, hv * d), bf16)],
           "G": [jax.ShapeDtypeStruct((b, length, hv), f32)],
           "Beta": [jax.ShapeDtypeStruct((b, length, hv), f32)],
           "State": [jax.ShapeDtypeStruct((b, hv, d, d), f32)]}

    def step(ins):
        return kernel(None, ins, {"chunk": 64})

    module = jax.export.export(jax.jit(step), platforms=["tpu"])(
        ins).mlir_module()
    assert module.count("tpu_custom_call") == len(kernels)
    for name in kernels:
        assert 'kernel_name = "%s"' % name in module
    if kernels:     # the state's buffer is the new state's
        assert "output_tuple_indices = [1], operand_index = 5" in module
        assert _vmem_limit_stated(module) == gdn_step.vmem_limit((4, 32))


@pytest.mark.parametrize("length,kernels", [
    (1, ["gdn_step_r128_h30_k96_v192_b4"]), (128, [])])
def test_the_wide_delta_rule_step_kernel_lowers_for_tpu(length, kernels):
    """`gated_delta_rule` at olmohybrid-decode-pp4's shape (128 rows, 30
    heads of 96 x 192 on both sides, a float32 state with two heads side
    by side: [128, 15, 96, 384], no lane of it padding) lowered for the
    TPU from this CPU host: a step holds one Mosaic kernel whose name
    says the head's shape, over blocks of 4 rows' 30 heads (8.4 MiB a
    grid step, the VMEM limit that follows), the state its result's
    buffer; a block of 128 positions holds none."""
    from paddle_tpu.kernels import gdn_step
    from paddle_tpu.ops import registry

    kernel = registry.get_op_info("gated_delta_rule").kernel
    b, h, dk, dv = 128, 30, 96, 192
    f32, bf16 = jnp.float32, jnp.bfloat16
    ins = {"Q": [jax.ShapeDtypeStruct((b, length, h * dk), bf16)],
           "K": [jax.ShapeDtypeStruct((b, length, h * dk), bf16)],
           "V": [jax.ShapeDtypeStruct((b, length, h * dv), bf16)],
           "G": [jax.ShapeDtypeStruct((b, length, h), f32)],
           "Beta": [jax.ShapeDtypeStruct((b, length, h), f32)],
           "State": [jax.ShapeDtypeStruct((b, h // 2, dk, 2 * dv), f32)]}

    def step(ins):
        return kernel(None, ins, {"chunk": 64, "state_pack": 2})

    module = jax.export.export(jax.jit(step), platforms=["tpu"])(
        ins).mlir_module()
    assert module.count("tpu_custom_call") == len(kernels)
    for name in kernels:
        assert 'kernel_name = "%s"' % name in module
    if kernels:
        assert "output_tuple_indices = [1], operand_index = 5" in module
        assert _vmem_limit_stated(module) \
            == gdn_step.vmem_limit((4, 30), dk * dv * 4)


@pytest.mark.parametrize("length,kernels", [(1, ["kda_step_r128_h32_b4"]),
                                            (64, [])])
def test_the_channel_gated_step_kernel_lowers_for_tpu(length, kernels):
    """`gated_delta_rule` under a gate a key channel at
    ling3-decode-ep16's shape (128 rows, 32 heads of 128 on both sides,
    G [rows, T, 32 * 128]) lowered for the TPU from this CPU host: a
    step holds one Mosaic kernel named for the gate and the block, the
    state its result's buffer; a block of 64 positions holds none."""
    from paddle_tpu.kernels import gdn_step
    from paddle_tpu.ops import registry

    kernel = registry.get_op_info("gated_delta_rule").kernel
    b, h, d = 128, 32, 128
    f32, bf16 = jnp.float32, jnp.bfloat16
    ins = {name: [jax.ShapeDtypeStruct((b, length, h * d), bf16)]
           for name in "QKV"}
    ins.update(G=[jax.ShapeDtypeStruct((b, length, h * d), f32)],
               Beta=[jax.ShapeDtypeStruct((b, length, h), f32)],
               State=[jax.ShapeDtypeStruct((b, h, d, d), f32)])

    def step(ins):
        return kernel(None, ins, {"chunk": 64, "sub_chunk": 16})

    module = jax.export.export(jax.jit(step), platforms=["tpu"])(
        ins).mlir_module()
    assert module.count("tpu_custom_call") == len(kernels)
    for name in kernels:
        assert 'kernel_name = "%s"' % name in module
    if kernels:
        assert "output_tuple_indices = [1], operand_index = 5" in module
        assert _vmem_limit_stated(module) == gdn_step.vmem_limit((4, 32))
