"""The bytes and operations one decode step of a share whose linear
layers run the gated delta rule (benchmark/models/qwen3next_decode.py)
must move and do, from the configuration's sizes alone: what no
implementation can avoid, not what this one does.

A step of a Gated DeltaNet layer reads every value head's state [key
dim, value dim] float32 of every row once and writes it once (the rule
rewrites the state whole: there is no live part of it, as there is of a
cache), beside operands three orders below it: the normed query and key
of each key head, the value, the gate and beta of each value head, the
output (`rule_step`).  Its operations are on the state's elements, a
head: the decay (1), `S^T k` (2), the rank-one update (2), `S^T q` (2);
they are the vector unit's, counted against the matrix unit's peak only
to say that the step is bound by its bytes.  The convolution's tail is
read and written too (`tail_bytes`).

Beside that, as benchmark/flops/gqa_window.py counts for the share it
describes: every weight the chip holds outside the routed experts
(`fixed_weight_bytes`) and the live keys and values of the full layers
(`kv_step`).  Which of the held routed experts a step's rows reach is the
router's choice at run time and is not in a trace, so `step_bytes`
leaves them out and is a floor.
"""

LINEAR, FULL = "linear_attention", "full_attention"
STATE_ITEMSIZE = 4      # the recurrent state is float32 (the config's
                        # `assumed.state_dtype`)


def layer_types(cfg):
    return [FULL if (i + 1) % cfg["full_attention_interval"] == 0
            else LINEAR for i in range(cfg["num_hidden_layers"])]


def count(cfg, kind):
    return layer_types(cfg).count(kind)


def widths(cfg):
    """(key width, value width) of a linear layer's projections."""
    return (cfg["linear_num_key_heads"] * cfg["linear_key_head_dim"],
            cfg["linear_num_value_heads"] * cfg["linear_value_head_dim"])


def state_row_bytes(cfg):
    """A row's recurrent state, one linear layer."""
    return (cfg["linear_num_value_heads"] * cfg["linear_key_head_dim"]
            * cfg["linear_value_head_dim"] * STATE_ITEMSIZE)


def tail_row_bytes(cfg, itemsize):
    """A row's convolution tail, one linear layer."""
    key_width, value_width = widths(cfg)
    return ((cfg["linear_conv_kernel_dim"] - 1)
            * (2 * key_width + value_width) * itemsize)


def rule_step(cfg, batch):
    """{"flops", "bytes"} of the rule's step, every linear layer: the
    states read and written once; q and k a key head, v, the decay and
    beta a value head and the output, float32 as the kernel takes them;
    7 operations a state element."""
    key_width, value_width = widths(cfg)
    heads = cfg["linear_num_value_heads"]
    small = (2 * key_width + 2 * value_width + 2 * heads) * 4
    layers = count(cfg, LINEAR)
    return {"flops": layers * batch * 7 * state_row_bytes(cfg)
            // STATE_ITEMSIZE,
            "bytes": layers * batch * (2 * state_row_bytes(cfg) + small)}


def state_bytes(cfg, batch, tail_itemsize):
    """Bytes of state a step reads and writes: every linear layer's
    recurrent state and convolution tail, once each way."""
    return count(cfg, LINEAR) * batch * 2 * (
        state_row_bytes(cfg) + tail_row_bytes(cfg, tail_itemsize))


def linear_parameters(cfg):
    d = cfg["hidden_size"]
    key_width, value_width = widths(cfg)
    heads = cfg["linear_num_value_heads"]
    return (d * (2 * key_width + 2 * value_width)       # W_qkvz
            + d * 2 * heads                             # W_ba
            + (2 * key_width + value_width)
            * cfg["linear_conv_kernel_dim"]             # the filter
            + 2 * heads + cfg["linear_value_head_dim"]  # A_log, dt_bias,
            + value_width * d)                          # the norm; W_o


def full_parameters(cfg):
    d, dim = cfg["hidden_size"], cfg["head_dim"]
    heads, kv_heads = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    return (d * 2 * heads * dim + 2 * d * kv_heads * dim + 2 * dim
            + heads * dim * d)


def expert_parameters(cfg):
    """One gated expert of the routed width (the shared one too)."""
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def shared_parameters(cfg):
    """What every layer holds outside its mixer and its routed experts:
    two norms, the router, the shared expert and its gate."""
    d = cfg["hidden_size"]
    return 2 * d + d * cfg["scored_experts"] + expert_parameters(cfg) + d


def chip_parameters(cfg):
    """Every parameter this chip holds."""
    d = cfg["hidden_size"]
    return (2 * cfg["vocab_size"] * d + d
            + count(cfg, LINEAR) * linear_parameters(cfg)
            + count(cfg, FULL) * full_parameters(cfg)
            + cfg["num_hidden_layers"] * (
                shared_parameters(cfg)
                + cfg["num_experts"] * expert_parameters(cfg)))


def fixed_weight_bytes(cfg, batch, itemsize):
    """Bytes of weights a step reads whatever its rows: every layer's
    mixer, norms, router and shared expert; the last norm and the head;
    of the embedding the rows looked up."""
    d = cfg["hidden_size"]
    return itemsize * (
        d + d * cfg["vocab_size"] + batch * d
        + count(cfg, LINEAR) * linear_parameters(cfg)
        + count(cfg, FULL) * full_parameters(cfg)
        + cfg["num_hidden_layers"] * shared_parameters(cfg))


def held_expert_bytes(cfg, batch, itemsize):
    """Bytes of the held routed experts a step's rows reach, in
    expectation over a router that spreads them evenly over the scored
    experts: not in `step_bytes` (a trace does not say which), said
    beside it."""
    miss = (1.0 - 1.0 / cfg["scored_experts"]) \
        ** (batch * cfg["num_experts_per_tok"])
    return (cfg["num_hidden_layers"] * cfg["num_experts"] * (1.0 - miss)
            * expert_parameters(cfg) * itemsize)


def kv_step(cfg, batch, position, itemsize):
    """{"flops", "bytes"} of the full layers' two products over the live
    slots in the step that writes `position`: the live keys and values
    read once, 2 FLOPs a multiply-add for scores and for values, every
    query head."""
    live = count(cfg, FULL) * (position + 1)
    return {"flops": 2 * 2 * batch * cfg["num_attention_heads"]
            * cfg["head_dim"] * live,
            "bytes": batch * live * 2 * cfg["num_key_value_heads"]
            * cfg["head_dim"] * itemsize}


def step_bytes(cfg, batch, position, weight_itemsize, cache_itemsize):
    """A floor of the bytes the step that writes slot `position` moves:
    the fixed weights, the states read and written, the live keys and
    values (the routed experts a row reached are left out)."""
    return (fixed_weight_bytes(cfg, batch, weight_itemsize)
            + state_bytes(cfg, batch, weight_itemsize)
            + kv_step(cfg, batch, position, cache_itemsize)["bytes"])
