"""A mixture-of-experts decoder as a fluid Program: OLMoE
(arXiv:2409.02060; huggingface.co/allenai/OLMoE-1B-7B-0125-Instruct).

The block of `decoder_block.py` (RMSNorm, rotary positions, the
`flash_attention` op, no bias), pre-norm, with q and k normed over
their whole projection, and in the feed-forward's place a routed expert
layer (`fluid.layers.moe`: a float32 router, every token's `top_k` of
`n_experts` gated-SiLU experts computed for it, nothing dropped).  The
loss is the mean cross-entropy plus `aux_coef` times the layers'
load-balance losses plus `z_coef` times their router z-losses.  The
equations are in `models/reference/olmoe.py`, which the tests hold this
to.
"""

from .. import fluid
from ..fluid.param_attr import ParamAttr
from .decoder_block import (attention, head_cross_entropy, norm,
                            token_feeds)

__all__ = ["build_olmoe_program", "olmoe_param_names"]

_BLOCK_PARAMS = ("norm_1", "wq", "wk", "wv", "q_norm", "k_norm", "wo",
                 "norm_2", "router", "w_gate", "w_up", "w_down")


def olmoe_param_names(n_layer):
    """The parameters' names, laid out as the reference's `params`."""
    return {
        "embed": "embed.w",
        "blocks": [{w: "block_%d.%s" % (i, w) for w in _BLOCK_PARAMS}
                   for i in range(n_layer)],
        "norm_f": "norm_f",
        "head": "head.w",
    }


def build_olmoe_program(batch, seq_len, vocab_size, n_layer=2, n_head=4,
                        d_model=64, d_head=None, d_expert=32, n_experts=8,
                        top_k=2, eps=1e-5, rope_theta=1e4, aux_coef=0.01,
                        z_coef=0.001):
    """Returns (main, startup, avg_loss, parts): `parts` holds the
    Variables "logits" [batch, seq, vocab], "ce" (the mean
    cross-entropy), "lb" and "z" (the auxiliary losses summed over
    layers, before their coefficients), and per layer, in lists,
    "moe_out", "router_logits", "top_w", "top_idx" and "counts".

    Feeds: tokens/positions int64 [batch, seq_len], targets int64
    [batch, seq_len, 1] (`transformer_program_feeds`).
    """
    d_head = d_head or d_model // n_head
    names = olmoe_param_names(n_layer)
    main = fluid.Program()
    startup = fluid.Program()
    with fluid.program_guard(main, startup):
        tokens, positions, targets = token_feeds(batch, seq_len)

        x = fluid.layers.embedding(
            tokens, size=[vocab_size, d_model],
            param_attr=ParamAttr(name=names["embed"]))
        parts = {"moe_out": [], "router_logits": [], "top_w": [],
                 "top_idx": [], "counts": []}
        lb = z = None
        for block in names["blocks"]:
            x = x + attention(norm(x, eps, block["norm_1"]), positions,
                              block, n_head, d_head, rope_theta,
                              qk_norm_eps=eps)
            m, lb_l, z_l, routing = fluid.layers.moe(
                norm(x, eps, block["norm_2"]), n_experts, d_expert, top_k,
                *(ParamAttr(name=block[w])
                  for w in ("router", "w_gate", "w_up", "w_down")))
            x = x + m
            lb = lb_l if lb is None else lb + lb_l
            z = z_l if z is None else z + z_l
            parts["moe_out"].append(m)
            parts["router_logits"].append(routing["logits"])
            for key in ("top_w", "top_idx", "counts"):
                parts[key].append(routing[key])

        logits, ce = head_cross_entropy(x, targets, eps, names["norm_f"],
                                        names["head"], vocab_size)
        avg_loss = ce + fluid.layers.scale(lb, scale=aux_coef) \
            + fluid.layers.scale(z, scale=z_coef)
        parts.update(logits=logits, ce=ce, lb=lb, z=z)
    return main, startup, avg_loss, parts
