"""The bytes and operations one decode step of granite-4.0-h-small's
share (benchmark/models/granite_small_decode.py) must move and do, from
the configuration's sizes alone: what no implementation can avoid, not
what this one does.

`step` is the Mamba-2 recurrence's alone, every mamba layer: a row's
state, `mamba_n_heads` x `mamba_d_head` x `mamba_d_state` float32, read
once and written once (the state's own bytes, no lane of padding), the
row's operands beside it at their own sizes as float32 (x and y a head
lane, dt and the decay a head, B and C a state entry: nothing a head's
scalar is spread over), and 6 operations a state element (the decay's
product, the outer product's product and its sum into the state, the
read's product and its sum, and the decay's share of an exponential a
head is not counted).  `step_bytes` is the
whole step's: the states and tails both ways, every weight the chip
holds once (a share's held experts among them: at 64 rows of ten experts
each over 72 the chance that a held expert has no row is 7e-5, so all 18
are read whole every step; of the table the rows the head reads, which
are all of them), and the attention layers' *live* keys and values at
the position the step writes.
"""

MAMBA, ATTENTION = "mamba", "attention"
STATE_ITEMSIZE = 4
STEP_FLOPS_AN_ELEMENT = 6


def layer_types(cfg):
    return tuple(cfg["layer_types"][:cfg["num_hidden_layers"]])


def count(cfg, kind):
    return layer_types(cfg).count(kind)


def inner(cfg):
    return cfg["mamba_n_heads"] * cfg["mamba_d_head"]


def state_row_bytes(cfg):
    """A row's state, a mamba layer: float32."""
    return inner(cfg) * cfg["mamba_d_state"] * STATE_ITEMSIZE


def tail_row_bytes(cfg, itemsize):
    return (cfg["mamba_d_conv"] - 1) \
        * (inner(cfg) + 2 * cfg["mamba_d_state"]) * itemsize


def step(cfg, batch):
    """{"flops", "bytes"} of the recurrence's step, every mamba layer:
    the states read and written once; x and y a head lane, dt and the
    decay a head, B and C a state entry, float32."""
    small = (2 * inner(cfg) + 2 * cfg["mamba_n_heads"]
             + 2 * cfg["mamba_d_state"]) * 4
    layers = count(cfg, MAMBA)
    return {"flops": layers * batch * STEP_FLOPS_AN_ELEMENT
            * state_row_bytes(cfg) // STATE_ITEMSIZE,
            "bytes": layers * batch * (2 * state_row_bytes(cfg) + small)}


def state_bytes(cfg, batch, tail_itemsize):
    """Bytes of state a step reads and writes: every mamba layer's state
    and convolution tail, once each way."""
    return count(cfg, MAMBA) * batch * 2 * (
        state_row_bytes(cfg) + tail_row_bytes(cfg, tail_itemsize))


def mamba_parameters(cfg):
    d, wide, entries = cfg["hidden_size"], inner(cfg), cfg["mamba_d_state"]
    heads, channels = cfg["mamba_n_heads"], inner(cfg) + 2 * entries
    return (d * (wide + channels + heads)               # W_in
            + channels * (cfg["mamba_d_conv"] + 1)      # the filter, bias
            + 3 * heads + wide                          # A_log, D, dt_bias
            + wide * d)                                 # norm_g; W_out


def attention_parameters(cfg):
    d, dim = cfg["hidden_size"], cfg["head_dim"]
    heads, kv_heads = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    return 2 * d * heads * dim + 2 * d * kv_heads * dim


def shared_parameters(cfg):
    """What every layer holds outside its mixer and its routed experts:
    the shared expert, the router and the two norms."""
    d = cfg["hidden_size"]
    return 3 * d * cfg["shared_intermediate_size"] \
        + d * cfg["scored_experts"] + 2 * d


def held_expert_parameters(cfg):
    """A layer's held routed experts."""
    return cfg["num_local_experts"] * 3 * cfg["hidden_size"] \
        * cfg["intermediate_size"]


def chip_parameters(cfg):
    """Every parameter this chip holds (the table is also the head)."""
    d = cfg["hidden_size"]
    return (cfg["vocab_size"] * d + d
            + count(cfg, MAMBA) * mamba_parameters(cfg)
            + count(cfg, ATTENTION) * attention_parameters(cfg)
            + cfg["num_hidden_layers"] * (shared_parameters(cfg)
                                          + held_expert_parameters(cfg)))


def weight_bytes(cfg, itemsize):
    """Bytes of weights a step reads: all the chip holds (the head reads
    the whole table, so the rows a step looks up are in it already)."""
    return itemsize * chip_parameters(cfg)


def kv_step(cfg, batch, position, itemsize):
    """Bytes of live keys and values the step that writes slot
    `position` reads, every attention layer."""
    return count(cfg, ATTENTION) * batch * (position + 1) * 2 \
        * cfg["num_key_value_heads"] * cfg["head_dim"] * itemsize


def step_bytes(cfg, batch, position, weight_itemsize, cache_itemsize):
    """The bytes the step that writes slot `position` must move."""
    return (weight_bytes(cfg, weight_itemsize)
            + state_bytes(cfg, batch, weight_itemsize)
            + kv_step(cfg, batch, position, cache_itemsize))
