"""Device milliseconds a step spends in the full layers' attention
kernels, forward and backward, of a program that mixes them with window
layers: the operations under the scope `attn_full`, which the
`flash_attention` op and its gradient op open around their kernels where
the op carries no window (`paddle_tpu/ops/attention.py`).  First device,
traced window, over its steps; beside `window_attn_ms_per_step`, whose
reader it shares.  A program with no window layer gets no value: all of
its attention is `attention_ms_per_step`'s."""

LAYER = "ops"
MOVES = "train_items_per_s"
UNIT = "ms"
SOURCE = "device_trace"


def read(run):
    window = run.lookup.module("layer_metrics", "window_attn_ms_per_step")
    return window.read(run, window.MIXED_WITH, window.SCOPE)
