"""Device milliseconds a pass of the block-diffusion cell's traced
generation call spends in the `cached_attention` op under the
block-causal mask (`kv_write`, `attn_block_causal`: on the kernel path
the walk of the live slots, `gqa_decode_*_b<B>`).  First device, inside
the calls' scans of blocks, over all of a call's passes (denoising and
commit alike), so that the parts add up to `diffusion_pass_ms`.  Prints
the other parts beside it."""

from benchmark.reduce import diffusion_ops

LAYER = "ops"
MOVES = "decode_tok_per_s"
UNIT = "ms"
SOURCE = "device_trace"


def read(run):
    return diffusion_ops.report(run, "attention")
