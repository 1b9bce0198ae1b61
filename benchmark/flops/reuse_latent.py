"""The bytes and operations one decode step of a sparse latent-attention
share whose layers do not all choose for themselves
(benchmark/models/hy4_decode.py) must move and do, from the
configuration's sizes: what no implementation can avoid, not what this
one does.  benchmark/flops/sparse_latent.py counts a chooser on every
layer (`index_step` times `num_hidden_layers`, the chooser's weights in
every layer's attention); here only the layers `indexer_types` calls
full hold one and score the live keys, and the others attend an
inherited set for nothing.

A step's choosers read the *live* index keys on the layers that choose
(`index_step`); its attention reads the *chosen* latents on every layer
(sparse_latent's `attend_step`, which holds as it stands: every layer
attends `index_topk` slots whoever chose them); and it reads, whatever
the batch, every weight the chip holds outside the routed experts
(`fixed_weight_bytes`): the latent attention with its output gate and
its sinks, the chooser where there is one, both hyper-connections'
float32 parameters, the dense feed-forward or the shared expert with the
router and its bias, the last norm and the head.  Which of the held
routed experts a step's few rows reach is the router's choice at run
time, so `step_bytes` leaves them out and is a floor.
"""

from benchmark.flops import sparse_latent

attend_step = sparse_latent.attend_step


def layer_kinds(cfg):
    """(`indexer_types`, `mlp_layer_types`) of the layers served."""
    layers = cfg["num_hidden_layers"]
    return cfg["indexer_types"][:layers], cfg["mlp_layer_types"][:layers]


def choosing_layers(cfg):
    return layer_kinds(cfg)[0].count("full")


def index_step(cfg, batch, position, itemsize):
    """{"flops", "bytes"} of the choosers' scores in the step that writes
    slot `position`, on the layers that choose alone: 2 FLOPs a
    multiply-add, the live slots' keys read once."""
    live = position + 1
    layers = choosing_layers(cfg)
    return {"flops": layers * 2 * batch * cfg["index_n_heads"]
            * cfg["index_head_dim"] * live,
            "bytes": layers * batch * live * cfg["index_head_dim"]
            * itemsize}


def attention_parameters(cfg):
    """One layer's attention sub-layer without a chooser: the latent
    attention, its output gate and its norms (the sinks are float32 and
    counted apart)."""
    d, heads = cfg["hidden_size"], cfg["num_attention_heads"]
    q, kv = cfg["q_lora_rank"], cfg["kv_lora_rank"]
    nope, rope, v = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                     cfg["v_head_dim"])
    return (d + d * q + q                       # input norm, W_dq, q norm
            + q * heads * (nope + rope)         # W_uq
            + d * (kv + rope) + kv              # W_dkv, kv norm
            + kv * heads * (nope + v)           # W_uk, W_uv
            + d * heads * v                     # W_g
            + heads * v * d)                    # W_o


def chooser_parameters(cfg):
    d, q = cfg["hidden_size"], cfg["q_lora_rank"]
    ih, idim = cfg["index_n_heads"], cfg["index_head_dim"]
    return q * ih * idim + d * idim + 2 * idim + d * ih


def stream_parameters(cfg):
    """One sub-layer's hyper-connection: the projections, three scalars
    and the biases, float32."""
    n = cfg["hc_mult"]
    maps = n * n + 2 * n
    return n * cfg["hidden_size"] * maps + 3 + maps


def float32_parameters(cfg):
    """What the step reads in float32 whatever the served type: two
    hyper-connections and the sinks a layer, a selection bias an expert
    layer."""
    layers = cfg["num_hidden_layers"]
    sparse = layer_kinds(cfg)[1].count("sparse")
    return layers * (2 * stream_parameters(cfg)
                     + cfg["num_attention_heads"]) \
        + sparse * cfg["scored_experts"]


def fixed_weight_bytes(cfg, batch, itemsize):
    """Bytes of weights a step reads whatever its rows."""
    d = cfg["hidden_size"]
    layers = cfg["num_hidden_layers"]
    dense = layer_kinds(cfg)[1].count("dense")
    served = layers * (attention_parameters(cfg) + d) \
        + choosing_layers(cfg) * chooser_parameters(cfg) \
        + dense * 3 * d * cfg["intermediate_size"] \
        + (layers - dense) * (3 * d * cfg["moe_intermediate_size"]
                              + d * cfg["scored_experts"]) \
        + d + d * cfg["vocab_size"] + batch * d
    return served * itemsize + float32_parameters(cfg) * 4


def step_bytes(cfg, batch, position, weight_itemsize, cache_itemsize,
               index_itemsize):
    """A floor of the bytes the step that writes slot `position` moves:
    the fixed weights, the live index keys of the layers that choose,
    the chosen latents of every layer (the routed experts a row reached
    are left out: the module's docstring)."""
    return (fixed_weight_bytes(cfg, batch, weight_itemsize)
            + index_step(cfg, batch, position, index_itemsize)["bytes"]
            + attend_step(cfg, batch, position, cache_itemsize)["bytes"])
