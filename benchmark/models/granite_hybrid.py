"""granite-4.0-h-micro's state-space / attention hybrid decoder as a
fluid Program, from a configuration file.

The network is the program's own
`paddle_tpu.models.hybrid_program.build_granite_hybrid_program` (per
`layer_types` a Mamba-2 mixer, `causal_conv1d` and the `ssd_scan` op
with a gated RMSNorm, or grouped-query attention without positions
through the `flash_attention` op; a gated-SiLU feed-forward after every
mixer; the embedding tied to the head; the three multipliers); this file
asks for it at the configuration's sizes, adds the configuration's
optimizer, and hands the plain reference
(benchmark/reference/granite_hybrid.py) the parameters' names in its
layout.
"""

FEED_NAMES = ("tokens", "targets")


def program_sizes(cfg):
    """The configuration's keys as `build_granite_hybrid_program`'s
    arguments."""
    if cfg["mamba_n_groups"] != 1:
        raise ValueError("granite builder: more than one B/C group is not "
                         "built")
    if cfg["num_local_experts"] or cfg["attention_bias"] \
            or cfg["mamba_proj_bias"] or not cfg["mamba_conv_bias"] \
            or not cfg["tie_word_embeddings"] \
            or cfg["position_embedding_type"] != "nope":
        raise ValueError("granite builder: routed experts, projection "
                         "biases, a convolution without bias, an untied "
                         "head and rotary positions are not built")
    inner = cfg["mamba_expand"] * cfg["hidden_size"]
    if inner != cfg["mamba_n_heads"] * cfg["mamba_d_head"]:
        raise ValueError("granite builder: mamba_expand * hidden_size is "
                         "not mamba_n_heads * mamba_d_head")
    if len(cfg["layer_types"]) != cfg["num_hidden_layers"]:
        raise ValueError("granite builder: layer_types names %d layers, "
                         "num_hidden_layers %d"
                         % (len(cfg["layer_types"]),
                            cfg["num_hidden_layers"]))
    return dict(
        seq_len=cfg["sequence_length"], vocab_size=cfg["vocab_size"],
        layer_types=tuple(cfg["layer_types"]), d_model=cfg["hidden_size"],
        d_ff=cfg["shared_intermediate_size"],
        n_head=cfg["num_attention_heads"],
        n_kv_head=cfg["num_key_value_heads"],
        d_head=cfg["hidden_size"] // cfg["num_attention_heads"],
        mamba_heads=cfg["mamba_n_heads"], mamba_d_head=cfg["mamba_d_head"],
        d_state=cfg["mamba_d_state"], d_conv=cfg["mamba_d_conv"],
        chunk=cfg["mamba_chunk_size"], eps=cfg["rms_norm_eps"],
        sm_scale=cfg["attention_multiplier"],
        embedding_multiplier=cfg["embedding_multiplier"],
        residual_multiplier=cfg["residual_multiplier"],
        logits_scaling=cfg["logits_scaling"])


def build(cfg, batch, train):
    import jax
    import paddle_tpu.fluid as fluid
    from paddle_tpu.models.hybrid_program import (
        build_granite_hybrid_program, granite_hybrid_param_names)

    if not train:
        raise ValueError("granite builder: only the training program "
                         "exists")
    seq = cfg["sequence_length"]
    main, startup, loss, _ = build_granite_hybrid_program(
        batch, **program_sizes(cfg))
    opt = cfg["optimizer"]
    if opt["type"] != "adam":
        raise ValueError("granite builder: optimizer %r" % opt["type"])
    with fluid.program_guard(main, startup):
        fluid.optimizer.Adam(
            learning_rate=opt["learning_rate"], beta1=opt["beta1"],
            beta2=opt["beta2"], epsilon=opt["epsilon"]).minimize(loss)
    names = granite_hybrid_param_names(cfg["layer_types"])
    built = {p.name for p in main.global_block().all_parameters()}
    if set(jax.tree_util.tree_leaves(names)) != built:
        raise ValueError("the program's parameters %s are not those "
                         "configuration %r names"
                         % (sorted(built), cfg["name"]))
    return {"main": main, "startup": startup,
            "feed_names": list(FEED_NAMES), "fetch": loss,
            "param_names": names, "items_per_step": batch * seq}


def sample(cfg, batch, key):
    """One seeded batch (pure jax): uniform tokens, each position's
    target the token that follows it; the model has no positions."""
    import jax
    import jax.numpy as jnp

    seq = cfg["sequence_length"]
    text = jax.random.randint(key, (batch, seq + 1), 0,
                              cfg["vocab_size"], jnp.int32)
    return {"tokens": text[:, :-1], "targets": text[:, 1:, None]}
