"""Device milliseconds a pass of the block-diffusion cell's traced
generation call spends in the expert layers (`moe_router`: its product,
the softmax over all 128 experts and the top-8; `moe_experts`: the
assignments' ordering, the grouped products over every expert that has a
row, the weighted combine).  First device, inside the calls' scans of
blocks, over all of a call's passes (denoising and commit alike), so
that the parts add up to `diffusion_pass_ms`.  Prints the other parts
beside it."""

from benchmark.reduce import diffusion_ops

LAYER = "ops"
MOVES = "decode_tok_per_s"
UNIT = "ms"
SOURCE = "device_trace"


def read(run):
    return diffusion_ops.report(run, "experts")
