"""A window/full mixture-of-experts decoder as a fluid training Program:
SmallThinker-21BA3B-Instruct
(huggingface.co/PowerInfer/SmallThinker-21BA3B-Instruct).

The block of `decoder_block.py` (RMSNorm, the `flash_attention` op over
grouped key/value heads, no bias), pre-norm, in a pattern of layers: a
layer is rotated or not (`rope_layout`) and attends its last `window`
positions or all of them (`window_layout`; the published pattern is a
full layer with no positions followed by three windowed, rotated ones).
In the feed-forward's place a routed expert layer (`fluid.layers.moe`)
of ReGLU experts whose router reads the layer's input norm, the tensor
attention reads, and not the tensor the experts read: the choice of a
token's experts does not wait for attention.  The chosen experts'
softmax probabilities are renormalised over the chosen; no shared
expert, no auxiliary loss in the objective (the router learns through
the chosen weights alone).  `held` = (first, count) makes every expert
layer one chip's share of an expert-parallel one (ops/moe.py): the
partial result goes on, forward and backward.  The loss is the mean
cross-entropy.  The equations are in `models/reference/smallthinker.py`,
which the tests hold this to.
"""

from .. import fluid
from ..fluid.param_attr import ParamAttr
from .decoder_block import (attention, head_cross_entropy, norm,
                            token_feeds)

__all__ = ["build_smallthinker_program", "smallthinker_param_names"]

_BLOCK_PARAMS = ("norm_1", "wq", "wk", "wv", "wo", "norm_2", "router",
                 "w_gate", "w_up", "w_down")


def smallthinker_param_names(n_layer):
    """The parameters' names, laid out as the reference's `params`."""
    return {
        "embed": "embed.w",
        "blocks": [{w: "block_%d.%s" % (i, w) for w in _BLOCK_PARAMS}
                   for i in range(n_layer)],
        "norm_f": "norm_f",
        "head": "head.w",
    }


def build_smallthinker_program(batch, seq_len, vocab_size,
                               rope_layout=(0, 1, 1, 1),
                               window_layout=(0, 1, 1, 1), window=16,
                               n_head=4, n_kv_head=2, d_model=64,
                               d_head=None, d_expert=32, n_experts=8,
                               top_k=2, held=None, eps=1e-6,
                               rope_theta=1.5e6, embed_std=None):
    """Returns (main, startup, avg_loss, parts): `parts` holds the
    Variables "logits" [batch, seq, vocab] and per layer, in lists,
    "attn_out" (the attention sub-layer's output, before the residual),
    "moe_in" (what the experts read), "moe_out", "router_logits",
    "top_w", "top_idx" and "counts".  One layer for each entry of
    `rope_layout` / `window_layout`.  With `embed_std` the embedding is
    drawn N(0, `embed_std`) and not by the stack's default (Xavier over
    [vocabulary, hidden], rows of about 0.01): under a row that small
    the first full layer's output, which late positions nearly share,
    is most of the residual stream, every later router reads nearly the
    same vector for every token, and the routers collapse.

    Feeds: tokens/positions int64 [batch, seq_len], targets int64
    [batch, seq_len, 1] (`transformer_program_feeds`).
    """
    if len(rope_layout) != len(window_layout):
        raise ValueError("smallthinker: %d layers are rotated or not, %d "
                         "windowed or not"
                         % (len(rope_layout), len(window_layout)))
    d_head = d_head or d_model // n_head
    names = smallthinker_param_names(len(rope_layout))
    main = fluid.Program()
    startup = fluid.Program()
    with fluid.program_guard(main, startup):
        tokens, positions, targets = token_feeds(batch, seq_len)
        x = fluid.layers.embedding(
            tokens, size=[vocab_size, d_model],
            param_attr=ParamAttr(
                name=names["embed"],
                initializer=None if embed_std is None
                else fluid.initializer.Normal(0.0, embed_std)))
        parts = {"attn_out": [], "moe_in": [], "moe_out": [],
                 "router_logits": [], "top_w": [], "top_idx": [],
                 "counts": []}
        for block, rotated, windowed in zip(names["blocks"], rope_layout,
                                            window_layout):
            u = norm(x, eps, block["norm_1"])
            a = attention(u, positions, block, n_head, d_head,
                          rope_theta if rotated else None,
                          n_kv_head=n_kv_head,
                          window=window if windowed else 0)
            x = x + a
            s = norm(x, eps, block["norm_2"])
            m, _, _, routing = fluid.layers.moe(
                s, n_experts, d_expert, top_k,
                *(ParamAttr(name=block[w])
                  for w in ("router", "w_gate", "w_up", "w_down")),
                norm_topk=True, held=held, activation="relu",
                router_input=u)
            x = x + m
            parts["attn_out"].append(a)
            parts["moe_in"].append(s)
            parts["moe_out"].append(m)
            parts["router_logits"].append(routing["logits"])
            for key in ("top_w", "top_idx", "counts"):
                parts[key].append(routing[key])
        logits, avg_loss = head_cross_entropy(
            x, targets, eps, names["norm_f"], names["head"], vocab_size)
        parts["logits"] = logits
    return main, startup, avg_loss, parts
