"""The bytes and operations one decode step of a sparse latent-attention
share (benchmark/models/dsv32_decode.py) must move and do, from the
configuration's sizes: what no implementation can avoid, not what this
one does.  No count holds bytes the step need not move: a slot past the
position, a latent that was not chosen, a held expert with no row.

A step's chooser reads the *live* index keys (`index_head_dim` values of
the positions up to the one it writes, every layer, every row) and
scores them with `index_n_heads` heads: `index_step`.  Its attention
reads the *chosen* latents (`min(index_topk, live)` entries of
`kv_lora_rank + qk_rope_head_dim` values, every layer, every row) for
two contractions in the absorbed form: `attend_step`.  And the step
reads, whatever the batch, every weight the chip holds outside the
routed experts (`fixed_weight_bytes`): which of the held routed experts
a step's few rows reach is the router's choice at run time (16 rows x 8
choices over 256 experts reach about 6 of the held 16 a layer) and is
not in a trace, so `step_bytes` leaves the routed experts out and is a
floor of what the step moves.
"""


def index_step(cfg, batch, position, itemsize):
    """{"flops", "bytes"} of the chooser's scores in the step that
    writes slot `position`, every layer: 2 FLOPs a multiply-add, the
    live slots' keys read once."""
    live = position + 1
    layers = cfg["num_hidden_layers"]
    return {"flops": layers * 2 * batch * cfg["index_n_heads"]
            * cfg["index_head_dim"] * live,
            "bytes": layers * batch * live * cfg["index_head_dim"]
            * itemsize}


def latent_width(cfg):
    return cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"]


def attend_step(cfg, batch, position, itemsize):
    """{"flops", "bytes"} of the attention's two contractions over the
    chosen latents in the step that writes slot `position`, every layer:
    scores heads x (latent + rope) a chosen slot, values heads x latent a
    chosen slot, the chosen latents read once."""
    chosen = min(cfg["index_topk"], position + 1)
    heads, layers = cfg["num_attention_heads"], cfg["num_hidden_layers"]
    scores = 2 * batch * heads * latent_width(cfg) * chosen
    values = 2 * batch * heads * cfg["kv_lora_rank"] * chosen
    return {"flops": layers * (scores + values),
            "bytes": layers * batch * chosen * latent_width(cfg) * itemsize}


def attention_parameters(cfg):
    """One layer's attention sub-layer, its chooser among it."""
    d, heads = cfg["hidden_size"], cfg["num_attention_heads"]
    q, kv = cfg["q_lora_rank"], cfg["kv_lora_rank"]
    nope, rope, v = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                     cfg["v_head_dim"])
    ih, idim = cfg["index_n_heads"], cfg["index_head_dim"]
    return (d + d * q + q                       # input norm, W_dq, q norm
            + q * heads * (nope + rope)         # W_uq
            + d * (kv + rope) + kv              # W_dkv, kv norm
            + kv * heads * (nope + v)           # W_uk, W_uv
            + heads * v * d                     # W_o
            + q * ih * idim + d * idim + 2 * idim + d * ih)  # the chooser


def fixed_weight_bytes(cfg, batch, itemsize):
    """Bytes of weights a step reads whatever its rows: every layer's
    attention, its pre-feed-forward norm, the dense feed-forward or the
    shared expert with the router and its bias; the last norm and the
    head; of the embedding the rows looked up."""
    d = cfg["hidden_size"]
    dense = cfg["first_k_dense_replace"]
    layers = cfg["num_hidden_layers"]
    a_layer = attention_parameters(cfg) + d
    total = layers * a_layer \
        + dense * 3 * d * cfg["intermediate_size"] \
        + (layers - dense) * (3 * d * cfg["moe_intermediate_size"]
                              + (d + 1) * cfg["scored_experts"])
    return (total + d + d * cfg["vocab_size"] + batch * d) * itemsize


def step_bytes(cfg, batch, position, weight_itemsize, cache_itemsize,
               index_itemsize):
    """A floor of the bytes the step that writes slot `position` moves:
    the fixed weights, the live index keys, the chosen latents (the
    routed experts a row reached are left out: the module's
    docstring)."""
    return (fixed_weight_bytes(cfg, batch, weight_itemsize)
            + index_step(cfg, batch, position, index_itemsize)["bytes"]
            + attend_step(cfg, batch, position, cache_itemsize)["bytes"])
