"""Milliseconds of the first device's time one pass of generation by
diffusion over blocks takes: the seconds an operation ran inside the
traced call's scan of blocks (the outermost `while` that holds the
operations under `decode_steps`, `models/decode.py
block_diffusion_decode`) over the call's passes, denoising and commit
alike (the call's own count: 768 + 192 at the cell's size).  A pass, not
a step: it feeds B positions of every row and yields none, one or
several tokens.  Nothing of the host or of the prefill is in it."""

from benchmark.reduce import diffusion_ops

LAYER = "decoding"
MOVES = "decode_tok_per_s"
UNIT = "ms"
SOURCE = "device_trace"


def read(run):
    each = diffusion_ops.pass_seconds(run)
    if each is None:
        return None
    denoise, commit = diffusion_ops.passes(run)
    print("diffusion pass on the device: %.4f ms over %d passes a call (%d "
          "that denoise, %d that commit)"
          % (each * 1e3, denoise + commit, denoise, commit), flush=True)
    return each * 1e3
