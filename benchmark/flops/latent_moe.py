"""The bytes and operations one decode step of a latent-attention
mixture-of-experts share (benchmark/models/pangu_decode.py) must move
and do, from the configuration's sizes: what no implementation can
avoid, not what this one does.

A step reads every weight the chip holds once whatever the batch (every
layer's attention matrices and norms, the dense feed-forward or the
shared expert, the router and the held routed experts that were given a
row; the last norm and the head; of the embedding only the rows it looks
up), reads the *live* part of the latent cache (the `kv_lora_rank +
qk_rope_head_dim` values of the positions up to the one it writes, every
layer, every row) and writes this position's.  Logits and activations
are three orders of magnitude below and are not counted.  A cache slot
past the live length holds nothing and need not be read: an
implementation that attends the whole extent under a mask moves more
than this.

The attention's two contractions over the cache (scores: heads x
(latent + rope) a live slot; values: heads x latent a live slot; in the
absorbed form, which is the cheaper one at decode) are the step's only
operations worth counting beside its bytes: `mla_step`.
"""


def attention_parameters(cfg):
    d, heads = cfg["hidden_size"], cfg["num_attention_heads"]
    q, kv = cfg["q_lora_rank"], cfg["kv_lora_rank"]
    nope, rope, v = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                     cfg["v_head_dim"])
    return (d + d * q + q                       # input norm, W_dq, q norm
            + q * heads * (nope + rope)         # W_uq
            + d * (kv + rope) + kv              # W_dkv, kv norm
            + kv * heads * (nope + v)           # W_uk, W_uv
            + heads * v * d                     # W_o
            + 3 * d)                            # the three other norms


def expert_parameters(cfg):
    """One gated expert of the routed width (the shared one too)."""
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def experts_with_a_row(cfg, batch):
    """The held experts a step's `batch * top_k` assignments reach, in
    expectation over a router that spreads them evenly over the scored
    experts."""
    miss = (1.0 - 1.0 / cfg["scored_experts"]) \
        ** (batch * cfg["num_experts_per_tok"])
    return cfg["n_routed_experts"] * (1.0 - miss)


def layer_parameters(cfg, layer, batch):
    """Parameters of layer `layer` one step reads."""
    d = cfg["hidden_size"]
    if layer < cfg["first_k_dense_replace"]:
        return attention_parameters(cfg) + 3 * d * cfg["intermediate_size"]
    return (attention_parameters(cfg) + expert_parameters(cfg)
            + d * cfg["scored_experts"]
            + experts_with_a_row(cfg, batch) * expert_parameters(cfg))


def weight_bytes(cfg, batch, itemsize):
    """Bytes of weights one step reads."""
    d = cfg["hidden_size"]
    layers = sum(layer_parameters(cfg, i, batch)
                 for i in range(cfg["num_hidden_layers"]))
    head = d + d * cfg["vocab_size"]
    return (layers + head + batch * d) * itemsize


def latent_width(cfg):
    return cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"]


def cache_bytes(cfg, batch, position, itemsize):
    """Bytes of latent cache the step that writes slot `position` moves:
    the `position` slots before it read, its own written, every layer,
    every row."""
    return (cfg["num_hidden_layers"] * batch * latent_width(cfg)
            * (position + 1) * itemsize)


def step_bytes(cfg, batch, position, weight_itemsize, cache_itemsize):
    return (weight_bytes(cfg, batch, weight_itemsize)
            + cache_bytes(cfg, batch, position, cache_itemsize))


def mean_step_bytes(cfg, batch, first, last, weight_itemsize,
                    cache_itemsize):
    """Mean of `step_bytes` over the steps that write slots `first` to
    `last`, both included (the cache term is linear in the slot)."""
    return step_bytes(cfg, batch, (first + last) / 2.0, weight_itemsize,
                      cache_itemsize)


def mla_step(cfg, batch, position, cache_itemsize):
    """{"flops", "bytes"} of the attention's two contractions over the
    cache in the step that writes slot `position`, every layer: 2 FLOPs
    a multiply-add, the live slots' latents read once."""
    heads, live = cfg["num_attention_heads"], position + 1
    scores = 2 * batch * heads * latent_width(cfg) * live
    values = 2 * batch * heads * cfg["kv_lora_rank"] * live
    return {"flops": cfg["num_hidden_layers"] * (scores + values),
            "bytes": cache_bytes(cfg, batch, position, cache_itemsize)}


def held_expert_bytes(cfg, batch, itemsize):
    """Bytes of the held routed experts' weights a step reads: those
    that were given a row, once, every expert layer."""
    layers = cfg["num_hidden_layers"] - cfg["first_k_dense_replace"]
    return (layers * experts_with_a_row(cfg, batch)
            * expert_parameters(cfg) * itemsize)
