"""Device milliseconds a pass of the block-diffusion cell's traced
generation call spends in the rule that fixes positions by confidence
(`diffusion_unmask`: the float32 logits' argmax, two reductions for the
chosen token's probability, the ranking of a block's masked positions).
First device, inside the calls' scans of blocks, over all of a call's
passes (denoising and commit alike), so that the parts add up to
`diffusion_pass_ms`.  Prints the other parts beside it."""

from benchmark.reduce import diffusion_ops

LAYER = "decoding"
MOVES = "decode_tok_per_s"
UNIT = "ms"
SOURCE = "device_trace"


def read(run):
    return diffusion_ops.report(run, "unmask")
