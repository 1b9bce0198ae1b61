"""A looped decoder of the post-2023 block as a fluid Program: Ouro's
LoopLM (arXiv:2510.25741; huggingface.co/ByteDance/Ouro-2.6B).

The block is what open models since 2023 are made of — RMSNorm (here as a
sandwich: before and after each sub-layer), rotary positions, a gated
SiLU feed-forward, no bias — built from `fluid.layers` alone: `rms_norm`,
`rope`, `fc(bias_attr=False)` (`mul` ops), `swish`, `flash_attention`.  A
stack of `n_layer` blocks is applied `n_loop` times over the same
weights: every parameter is created once by name (`ParamAttr(name=...)`)
and read by `n_loop` ops, so the start-up program initialises it once,
`append_backward` sums `n_loop` gradient contributions into it and the
optimizer updates it once.  After every pass the last norm, the untied
head and a per-token exit gate give that pass's logits and exit
probability; the loss is the expected cross-entropy under the exit
distribution less `exit_entropy_beta` times its entropy.  The equations
are in `models/reference/ouro.py`, which the tests hold this to.
"""

from .. import fluid
from ..fluid.param_attr import ParamAttr
from .decoder_block import attention as _attention, linear as _linear, \
    norm as _norm

__all__ = ["build_looped_program", "looped_param_names"]

_BLOCK_NORMS = ("norm_1", "norm_2", "norm_3", "norm_4")
_BLOCK_MATRICES = ("wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down")


def looped_param_names(n_layer):
    """The parameters' names, laid out as the reference's `params`."""
    return {
        "embed": "embed.w",
        "blocks": [{w: "block_%d.%s" % (i, w)
                    for w in _BLOCK_NORMS + _BLOCK_MATRICES}
                   for i in range(n_layer)],
        "norm_f": "norm_f",
        "head": "head.w",
        "gate": ("exit_gate.w", "exit_gate.b"),
    }


def _block(x, positions, names, n_head, d_head, d_ff, eps, theta):
    d_model = x.shape[-1]
    a = _attention(_norm(x, eps, names["norm_1"]), positions, names, n_head,
                   d_head, theta)
    x = x + _norm(a, eps, names["norm_2"])

    h = _norm(x, eps, names["norm_3"])
    m = fluid.layers.swish(_linear(h, d_ff, names["w_gate"])) \
        * _linear(h, d_ff, names["w_up"])
    return x + _norm(_linear(m, d_model, names["w_down"]), eps,
                     names["norm_4"])


def build_looped_program(batch, seq_len, vocab_size, n_layer=2, n_loop=4,
                         n_head=4, d_model=64, d_head=None, d_ff=None,
                         eps=1e-6, rope_theta=1e6, exit_entropy_beta=0.1):
    """Returns (main, startup, avg_loss, passes): `passes` holds, per
    pass through the stack, the Variables "logits" [batch, seq, vocab],
    "lambdas" (the gate's exit probability), "ce" (each token's
    cross-entropy) and "exit_p" (its share of the exit distribution),
    the last three [batch * seq, 1] in float32.

    Feeds: tokens/positions int64 [batch, seq_len], targets int64
    [batch, seq_len, 1] (`transformer_program_feeds`).
    """
    if n_loop < 2:
        raise ValueError("build_looped_program: an exit distribution "
                         "needs at least two passes, got n_loop=%d"
                         % n_loop)
    d_head = d_head or d_model // n_head
    d_ff = d_ff or 4 * d_model
    names = looped_param_names(n_layer)
    main = fluid.Program()
    startup = fluid.Program()
    with fluid.program_guard(main, startup):
        tokens = fluid.layers.data(
            name="tokens", shape=[batch, seq_len], dtype="int64",
            append_batch_size=False)
        positions = fluid.layers.data(
            name="positions", shape=[batch, seq_len], dtype="int64",
            append_batch_size=False)
        targets = fluid.layers.data(
            name="targets", shape=[batch, seq_len, 1], dtype="int64",
            append_batch_size=False)
        flat_targets = fluid.layers.reshape(x=targets, shape=[-1, 1])

        x = fluid.layers.embedding(
            tokens, size=[vocab_size, d_model],
            param_attr=ParamAttr(name=names["embed"]))
        passes = {"logits": [], "lambdas": [], "ce": [], "exit_p": []}
        log_exit = []
        log_stay = None     # log prod_{j<t} (1 - lambda_j)
        for t in range(n_loop):
            for block in names["blocks"]:
                x = _block(x, positions, block, n_head, d_head, d_ff, eps,
                           rope_theta)
            x = _norm(x, eps, names["norm_f"])
            logits = _linear(x, vocab_size, names["head"])
            ce = fluid.layers.softmax_with_cross_entropy(
                fluid.layers.reshape(x=logits, shape=[-1, vocab_size]),
                flat_targets)
            # the gate's logit in float32 from here on, whatever the
            # compute type: one scalar a token
            g = fluid.layers.cast(fluid.layers.reshape(
                x=fluid.layers.fc(
                    input=x, size=1, num_flatten_dims=2,
                    param_attr=ParamAttr(name=names["gate"][0]),
                    bias_attr=ParamAttr(name=names["gate"][1])),
                shape=[-1, 1]), "float32")
            # log lambda = logsigmoid(g), log(1 - lambda) = logsigmoid(-g):
            # the exit distribution in logs, so that a saturated gate
            # gives p = 0 and p log p = 0, not a NaN
            last = t == n_loop - 1
            if last:
                log_p = log_stay
            else:
                log_p = fluid.layers.logsigmoid(g)
                if log_stay is not None:
                    log_p = log_p + log_stay
                stay = fluid.layers.logsigmoid(
                    fluid.layers.scale(g, scale=-1.0))
                log_stay = stay if log_stay is None else log_stay + stay
            passes["logits"].append(logits)
            passes["lambdas"].append(fluid.layers.sigmoid(g))
            passes["ce"].append(ce)
            passes["exit_p"].append(fluid.layers.exp(log_p))
            log_exit.append(log_p)

        per_token = None
        for p, ce, log_p in zip(passes["exit_p"], passes["ce"], log_exit):
            # p ce - beta H, H = -sum p log p
            term = p * (ce + fluid.layers.scale(log_p,
                                                scale=exit_entropy_beta))
            per_token = term if per_token is None else per_token + term
        avg_loss = fluid.layers.mean(x=per_token)
    return main, startup, avg_loss, passes
