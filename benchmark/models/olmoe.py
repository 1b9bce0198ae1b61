"""OLMoE's mixture-of-experts decoder as a fluid Program, from a
configuration file.

The network is the program's own
`paddle_tpu.models.moe_program.build_olmoe_program` (pre-norm blocks
with q/k RMSNorm, RoPE and the `flash_attention` op, and a routed expert
layer: a float32 router, every token's 8 of 64 gated-SiLU experts
computed for it, the load-balance and router z-losses); this file asks
for it at the configuration's sizes, adds the configuration's optimizer,
and hands the plain reference (benchmark/reference/olmoe.py) the
parameters' names in its layout.
"""

FEED_NAMES = ("tokens", "positions", "targets")


def program_sizes(cfg):
    """The configuration's keys as `build_olmoe_program`'s arguments."""
    if cfg["num_key_value_heads"] != cfg["num_attention_heads"]:
        raise ValueError("olmoe builder: grouped key/value heads are not "
                         "built")
    if cfg["norm_topk_prob"] or cfg["attention_bias"] or cfg["clip_qkv"] \
            or cfg["tie_word_embeddings"]:
        raise ValueError("olmoe builder: renormalised routing weights, "
                         "biases, clipped q/k/v and a tied head are not "
                         "built")
    return dict(
        seq_len=cfg["sequence_length"], vocab_size=cfg["vocab_size"],
        n_layer=cfg["num_hidden_layers"],
        n_head=cfg["num_attention_heads"], d_model=cfg["hidden_size"],
        d_head=cfg["hidden_size"] // cfg["num_attention_heads"],
        d_expert=cfg["intermediate_size"], n_experts=cfg["num_experts"],
        top_k=cfg["num_experts_per_tok"], eps=cfg["rms_norm_eps"],
        rope_theta=cfg["rope_theta"], aux_coef=cfg["aux_coef"],
        z_coef=cfg["z_coef"])


def build(cfg, batch, train):
    import jax
    import paddle_tpu.fluid as fluid
    from paddle_tpu.models.moe_program import (build_olmoe_program,
                                               olmoe_param_names)

    if not train:
        raise ValueError("olmoe builder: only the training program exists")
    seq = cfg["sequence_length"]
    main, startup, loss, _ = build_olmoe_program(batch, **program_sizes(cfg))
    opt = cfg["optimizer"]
    if opt["type"] != "adam":
        raise ValueError("olmoe builder: optimizer %r" % opt["type"])
    with fluid.program_guard(main, startup):
        fluid.optimizer.Adam(
            learning_rate=opt["learning_rate"], beta1=opt["beta1"],
            beta2=opt["beta2"], epsilon=opt["epsilon"]).minimize(loss)
    names = olmoe_param_names(cfg["num_hidden_layers"])
    built = {p.name for p in main.global_block().all_parameters()}
    if set(jax.tree_util.tree_leaves(names)) != built:
        raise ValueError("the program's parameters %s are not those "
                         "configuration %r names"
                         % (sorted(built), cfg["name"]))
    return {"main": main, "startup": startup,
            "feed_names": list(FEED_NAMES), "fetch": loss,
            "param_names": names, "items_per_step": batch * seq}


def sample(cfg, batch, key):
    """One seeded batch (pure jax): uniform tokens at positions
    0..seq-1, each position's target the token that follows it."""
    import jax
    import jax.numpy as jnp

    seq = cfg["sequence_length"]
    text = jax.random.randint(key, (batch, seq + 1), 0,
                              cfg["vocab_size"], jnp.int32)
    return {
        "tokens": text[:, :-1],
        "positions": jnp.broadcast_to(
            jnp.arange(seq, dtype=jnp.int32), (batch, seq)),
        "targets": text[:, 1:, None],
    }
