"""Device milliseconds the dense state cell's traced generation call's
prefill spends under the `gated_delta_rule` op: `gdn_prefill_ms_per_call`'s reading
(benchmark/layer_metrics/gdn_prefill_ms_per_call.py: the same scopes, kernels and
sizes, which this cell's configuration states under the same keys; at
96 x 192 a head the kernels are `gdn_step_r<rows>_h30_k96_v192_b<rows a
step>`, the prefix is the reader's) of a run of
benchmark/drivers/decode_dense_state.py, whose facts have names of their
own (benchmark/reduce/dense_state_ops.py says why).  Silent in every
other cell."""

from benchmark.reduce import dense_state_ops

LAYER = "ops"
MOVES = "decode_tok_per_s"
UNIT = "ms"
SOURCE = "device_trace"


def read(run):
    return dense_state_ops.as_state(run, "gdn_prefill_ms_per_call")
