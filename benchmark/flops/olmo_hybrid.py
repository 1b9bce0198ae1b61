"""The bytes and operations one decode step of Olmo-Hybrid's pipeline
stage (benchmark/models/olmohybrid_decode.py) must move and do, from the
configuration's sizes alone: what no implementation can avoid, not what
this one does.

A dense model: every weight the chip holds is read once a step whatever
the rows are (`weight_bytes`: every layer's mixer, norms and
feed-forward, the last norm and the head, of the embedding the rows
looked up), so the floor counts all of them and nothing is left out as a
routed share's experts are.  The linear layers' states and tails and the
rule's step are benchmark/flops/gated_delta.py's, which read this
configuration's keys as they are (`state_bytes`, `rule_step`: every
linear layer's 96 x 192 state a head read once and written once, the
state's own bytes, no lane of padding); the full layers' live keys and
values are its `kv_step` (30 key/value heads of 128, a query each).
"""

from benchmark.flops import gated_delta

LINEAR, FULL = gated_delta.LINEAR, gated_delta.FULL
count = gated_delta.count
state_row_bytes = gated_delta.state_row_bytes
state_bytes = gated_delta.state_bytes
rule_step = gated_delta.rule_step
kv_step = gated_delta.kv_step
linear_parameters = gated_delta.linear_parameters


def full_parameters(cfg):
    """A full layer's mixer: four projections (no gate) and the two
    whole-projection norms."""
    d, dim = cfg["hidden_size"], cfg["head_dim"]
    heads, kv_heads = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    return (d * heads * dim + 2 * d * kv_heads * dim + heads * dim * d
            + heads * dim + kv_heads * dim)


def layer_parameters(cfg):
    """What every layer holds outside its mixer: the gated feed-forward
    and the two norms."""
    d = cfg["hidden_size"]
    return 3 * d * cfg["intermediate_size"] + 2 * d


def chip_parameters(cfg):
    """Every parameter this chip holds."""
    d = cfg["hidden_size"]
    return (2 * cfg["vocab_size"] * d + d
            + count(cfg, LINEAR) * linear_parameters(cfg)
            + count(cfg, FULL) * full_parameters(cfg)
            + cfg["num_hidden_layers"] * layer_parameters(cfg))


def weight_bytes(cfg, batch, itemsize):
    """Bytes of weights a step reads: all of them but the embedding, of
    which the rows looked up."""
    d = cfg["hidden_size"]
    return itemsize * (chip_parameters(cfg) - cfg["vocab_size"] * d
                       + batch * d)


def step_bytes(cfg, batch, position, weight_itemsize, cache_itemsize):
    """The bytes the step that writes slot `position` must move: the
    weights, the states and tails read and written, the live keys and
    values."""
    return (weight_bytes(cfg, batch, weight_itemsize)
            + state_bytes(cfg, batch, weight_itemsize)
            + kv_step(cfg, batch, position, cache_itemsize)["bytes"])
