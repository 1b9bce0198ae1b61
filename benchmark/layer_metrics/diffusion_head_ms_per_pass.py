"""Device milliseconds a pass of the block-diffusion cell's traced
generation call spends in the product with the untied head, [rows x B,
hidden] x [hidden, vocabulary], which the denoising passes run and the
commit passes do not (nothing reads their logits).  First device, inside
the calls' scans of blocks, over all of a call's passes (denoising and
commit alike), so that the parts add up to `diffusion_pass_ms`.  Prints
the other parts beside it."""

from benchmark.reduce import diffusion_ops

LAYER = "ops"
MOVES = "decode_tok_per_s"
UNIT = "ms"
SOURCE = "device_trace"


def read(run):
    return diffusion_ops.report(run, "head")
