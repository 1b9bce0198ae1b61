"""The window flash-attention backward kernels' share of their roofline:
the least time the FLOPs the backward requires under the window take at
the chip's peaks (benchmark/flops/window_flash.py: four products an
attended pair; recomputing the scores is not counted), over the device
time of the operations named `flash_attention_bwd_*_w<W>*`, which are
the backward kernels that carry a window (one that makes dq, dk and dv,
or the pair `_dkv` / `_dq` that walks).  The forward's reader, given the
backward's names and costs.  A program with no window kernel gets no
value."""

from benchmark.flops import window_flash

LAYER = "kernels"
MOVES = "train_items_per_s"
UNIT = "%"
SOURCE = "device_trace"


def read(run):
    forward = run.lookup.module("layer_metrics", "window_flash_fwd_roofline")
    return forward.read(run, window_flash.BWD_NAME, "backward")
