"""The bytes and operations one decode step of a share whose linear
layers run the delta rule under a gate a key channel (Kimi Delta
Attention) beside latent attention (benchmark/models/ling3_decode.py)
must move and do, from the configuration's sizes alone: what no
implementation can avoid, not what this one does.

A step of a KDA layer reads every head's state [head_dim, head_dim]
float32 of every row once and writes it once (the rule rewrites the
state whole), beside operands two orders below it: the normed query and
key, the value and the **decay a key channel** of each head ([heads,
head_dim] float32 each, where a gate a head is one number), beta a head,
the output (`rule_step`).  Its operations are on the state's elements, a
head: the decay (1), `S^T k` (2), the rank-one update (2), `S^T q` (2);
they are the vector unit's, counted against the matrix unit's peak only
to say that the step is bound by its bytes.  The convolution's tail is
read and written too (`tail_row_bytes`).

Beside that: every weight the chip holds outside the routed experts
(`fixed_weight_bytes`: the KDA and latent mixers, the dense layers, the
routers and their biases, the shared experts, the norms, the head, the
embedding's rows looked up) and the live latents of the latent layers
(`latent_step`).  Which of the held routed experts a step's rows reach
is the router's choice at run time and is not in a trace, so
`step_bytes` leaves them out and is a floor (`held_expert_bytes` says
what an even router's would weigh).
"""

KDA, LATENT = "linear_attention", "latent_attention"
STATE_ITEMSIZE = 4      # the recurrent state is float32 (the config's
                        # `assumed.state_dtype`)


def layer_types(cfg):
    return [LATENT if (i + 1) % cfg["layer_group_size"] == 0 else KDA
            for i in range(cfg["num_hidden_layers"])]


def count(cfg, kind):
    return layer_types(cfg).count(kind)


def width(cfg):
    """The width of q, k, v and of the gate's projection of a KDA
    layer."""
    return cfg["num_attention_heads"] * cfg["head_dim"]


def state_row_bytes(cfg):
    """A row's recurrent state, one KDA layer."""
    return width(cfg) * cfg["head_dim"] * STATE_ITEMSIZE


def tail_row_bytes(cfg, itemsize):
    """A row's convolution tail, one KDA layer."""
    return (cfg["short_conv_kernel_size"] - 1) * 3 * width(cfg) * itemsize


def rule_step(cfg, batch):
    """{"flops", "bytes"} of the rule's step, every KDA layer: the
    states read and written once; q, k, v, the decay a key channel and
    the output [heads, head_dim] and beta [heads], float32 as the kernel
    takes them; 7 operations a state element."""
    small = (5 * width(cfg) + cfg["num_attention_heads"]) * 4
    layers = count(cfg, KDA)
    return {"flops": layers * batch * 7 * state_row_bytes(cfg)
            // STATE_ITEMSIZE,
            "bytes": layers * batch * (2 * state_row_bytes(cfg) + small)}


def state_bytes(cfg, batch, tail_itemsize):
    """Bytes of state a step reads and writes: every KDA layer's
    recurrent state and convolution tail, once each way."""
    return count(cfg, KDA) * batch * 2 * (
        state_row_bytes(cfg) + tail_row_bytes(cfg, tail_itemsize))


def kda_parameters(cfg):
    d, heads = cfg["hidden_size"], cfg["num_attention_heads"]
    return (d * 4 * width(cfg)                          # W_q, k, v, f
            + d * 2 * heads                             # W_b, W_z
            + 3 * width(cfg) * cfg["short_conv_kernel_size"]
            + heads + width(cfg) + cfg["head_dim"]      # A_log, dt_bias,
            + width(cfg) * d)                           # the norm; W_o


def latent_parameters(cfg):
    d, heads = cfg["hidden_size"], cfg["num_attention_heads"]
    rank, rope = cfg["kv_lora_rank"], cfg["qk_rope_head_dim"]
    return (d * heads * (cfg["qk_nope_head_dim"] + rope)        # W_q
            + d * (rank + rope) + rank                  # W_dkv, its norm
            + rank * heads * (cfg["qk_nope_head_dim"]
                              + cfg["v_head_dim"])      # W_uk, W_uv
            + d * heads + heads * cfg["v_head_dim"] * d)        # W_z, W_o


def expert_parameters(cfg):
    """One gated expert of the routed width (the shared one too)."""
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def feed_forward_parameters(cfg, layer):
    """What layer `layer` holds outside its mixer and its routed
    experts: two norms and the dense feed-forward, or the router, its
    bias and the shared expert."""
    d = cfg["hidden_size"]
    if layer < cfg["first_k_dense_replace"]:
        return 2 * d + 3 * d * cfg["intermediate_size"]
    return 2 * d + (d + 1) * cfg["scored_experts"] + expert_parameters(cfg)


def expert_layers(cfg):
    return cfg["num_hidden_layers"] - cfg["first_k_dense_replace"]


def chip_parameters(cfg):
    """Every parameter this chip holds."""
    d = cfg["hidden_size"]
    return (2 * cfg["vocab_size"] * d + d
            + count(cfg, KDA) * kda_parameters(cfg)
            + count(cfg, LATENT) * latent_parameters(cfg)
            + sum(feed_forward_parameters(cfg, i)
                  for i in range(cfg["num_hidden_layers"]))
            + expert_layers(cfg) * cfg["num_experts"]
            * expert_parameters(cfg))


def fixed_weight_bytes(cfg, batch, itemsize):
    """Bytes of weights a step reads whatever its rows: every layer's
    mixer, norms, dense feed-forward or router and shared expert; the
    last norm and the head; of the embedding the rows looked up."""
    d = cfg["hidden_size"]
    return itemsize * (
        d + d * cfg["vocab_size"] + batch * d
        + count(cfg, KDA) * kda_parameters(cfg)
        + count(cfg, LATENT) * latent_parameters(cfg)
        + sum(feed_forward_parameters(cfg, i)
              for i in range(cfg["num_hidden_layers"])))


def held_expert_bytes(cfg, batch, itemsize):
    """Bytes of the held routed experts a step's rows reach, in
    expectation over a router that spreads them evenly over the scored
    experts: not in `step_bytes` (a trace does not say which), said
    beside it."""
    miss = (1.0 - 1.0 / cfg["scored_experts"]) \
        ** (batch * cfg["num_experts_per_tok"])
    return (expert_layers(cfg) * cfg["num_experts"] * (1.0 - miss)
            * expert_parameters(cfg) * itemsize)


def latent_step(cfg, batch, position, itemsize):
    """{"flops", "bytes"} of the latent layers' two products over the
    live slots in the step that writes `position`: the live latents and
    shared keys read once, 2 FLOPs a multiply-add for the scores (over
    latent + rope) and for the values (over the latent), every head."""
    live = count(cfg, LATENT) * (position + 1)
    rank, rope = cfg["kv_lora_rank"], cfg["qk_rope_head_dim"]
    return {"flops": 2 * batch * cfg["num_attention_heads"] * live
            * (2 * rank + rope),
            "bytes": batch * live * (rank + rope) * itemsize}


def step_bytes(cfg, batch, position, weight_itemsize, cache_itemsize):
    """A floor of the bytes the step that writes slot `position` moves:
    the fixed weights, the states read and written, the live latents
    (the routed experts a row reached are left out)."""
    return (fixed_weight_bytes(cfg, batch, weight_itemsize)
            + state_bytes(cfg, batch, weight_itemsize)
            + latent_step(cfg, batch, position, cache_itemsize)["bytes"])
