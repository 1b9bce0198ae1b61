"""The bytes and operations one pass of generation by diffusion over
blocks must move and do on SDAR's pipeline stage
(benchmark/models/sdar_decode.py), from the configuration's sizes alone:
what no implementation can avoid, not what this one does.

A pass feeds `batch` rows x B positions.  Its weights: every layer's
attention, norms and router whole, and of a layer's experts those that
have a row (`experts_read`: with batch x B x experts-a-token assignments
spread evenly, E (1 - (1 - k / E)^tokens) of E in the mean: all of them
at the cell's 512 tokens); a denoising pass reads the head as well, a
commit pass does not (nothing reads its logits); of the embedding the
rows looked up.  Its cache: the live slots' keys and values of every
layer once (`live_slots`: the stored positions before the block and the
block's own, not the extent), the block's own written.  Logits, scores
and activations are not counted: an implementation may keep them on the
chip.  So the share a pass reaches of its floor cannot read over 100%.
"""


def layer_parameters(cfg):
    """(outside the experts, one expert) of a layer."""
    d, dim = cfg["hidden_size"], cfg["head_dim"]
    heads, kv_heads = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    outside = (d * heads * dim + 2 * d * kv_heads * dim + heads * dim * d
               + 2 * dim + 2 * d + d * cfg["num_experts"])
    return outside, 3 * d * cfg["moe_intermediate_size"]


def chip_parameters(cfg):
    """Every parameter this chip holds."""
    d = cfg["hidden_size"]
    outside, expert = layer_parameters(cfg)
    return (2 * cfg["vocab_size"] * d + d + cfg["num_hidden_layers"]
            * (outside + cfg["num_experts"] * expert))


def experts_read(cfg, tokens):
    """The experts of a layer that have a row among `tokens` tokens, in
    the mean under even routing."""
    experts, chosen = cfg["num_experts"], cfg["num_experts_per_tok"]
    return experts * (1.0 - (1.0 - chosen / experts) ** tokens)


def pass_weight_bytes(cfg, batch, itemsize, head_share=1.0):
    """Bytes of weights a pass over `batch` rows x B positions reads;
    `head_share`: the share of passes that read the head (the denoising
    ones)."""
    d, width = cfg["hidden_size"], cfg["generation"]["block_length"]
    tokens = batch * width
    outside, expert = layer_parameters(cfg)
    layers = cfg["num_hidden_layers"] * (
        outside + experts_read(cfg, tokens) * expert)
    return itemsize * (layers + d + tokens * d
                       + head_share * cfg["vocab_size"] * d)


def live_slots(cfg, prompt_len, gen_len):
    """The slots a pass attends, in the mean over a call's passes: block
    n of the generated ones stands at the prompt's whole blocks + n B
    and sees everything before it and itself."""
    width = cfg["generation"]["block_length"]
    whole = prompt_len // width * width
    blocks = -(-(prompt_len - whole + gen_len) // width)
    return whole + width * (blocks - 1) / 2.0 + width


def attention_pass(cfg, batch, slots, itemsize, layers=None):
    """{"bytes", "flops"} of the block-causal attention of one pass over
    `slots` live slots a row, `layers` layers (default: all): keys and
    values read once, the block's own written, the queries read and the
    output written; the scores' and the values' multiply-adds of B
    queries a head."""
    layers = cfg["num_hidden_layers"] if layers is None else layers
    dim, width = cfg["head_dim"], cfg["generation"]["block_length"]
    heads, kv_heads = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    moved = 2 * kv_heads * (slots + width) * dim + 2 * heads * width * dim
    return {"bytes": layers * batch * moved * itemsize,
            "flops": layers * batch * 4 * heads * width * slots * dim}


def pass_cost(cfg, batch, slots, weight_itemsize, cache_itemsize,
              head_share=1.0):
    """{"bytes", "flops"} of a whole pass."""
    d, width = cfg["hidden_size"], cfg["generation"]["block_length"]
    tokens = batch * width
    outside, expert = layer_parameters(cfg)
    attention = attention_pass(cfg, batch, slots, cache_itemsize)
    active = outside + cfg["num_experts_per_tok"] * expert
    return {
        "bytes": pass_weight_bytes(cfg, batch, weight_itemsize, head_share)
        + attention["bytes"],
        "flops": 2 * tokens * (cfg["num_hidden_layers"] * active
                               + head_share * cfg["vocab_size"] * d)
        + attention["flops"]}
