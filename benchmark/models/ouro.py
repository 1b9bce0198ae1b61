"""Ouro's looped decoder as a fluid Program, from a configuration file.

The network is the program's own
`paddle_tpu.models.looped_program.build_looped_program` (RMSNorm
sandwich, RoPE, SwiGLU, the `flash_attention` op, the stack applied
`total_ut_steps` times over shared weights, an exit gate and the
expected-loss objective); this file asks for it at the configuration's
sizes, adds the configuration's optimizer, and hands the plain reference
(benchmark/reference/ouro.py) the parameters' names in its layout.
"""

FEED_NAMES = ("tokens", "positions", "targets")


def program_sizes(cfg):
    """The configuration's keys as `build_looped_program`'s arguments."""
    if cfg["num_key_value_heads"] != cfg["num_attention_heads"]:
        raise ValueError("ouro builder: grouped key/value heads are not "
                         "built")
    return dict(
        seq_len=cfg["sequence_length"], vocab_size=cfg["vocab_size"],
        n_layer=cfg["num_hidden_layers"], n_loop=cfg["total_ut_steps"],
        n_head=cfg["num_attention_heads"], d_model=cfg["hidden_size"],
        d_head=cfg["head_dim"], d_ff=cfg["intermediate_size"],
        eps=cfg["rms_norm_eps"], rope_theta=cfg["rope_theta"],
        exit_entropy_beta=cfg["exit_entropy_beta"])


def build(cfg, batch, train):
    import jax
    import paddle_tpu.fluid as fluid
    from paddle_tpu.models.looped_program import (
        build_looped_program, looped_param_names)

    if not train:
        raise ValueError("ouro builder: only the training program exists")
    seq = cfg["sequence_length"]
    main, startup, loss, _ = build_looped_program(batch,
                                                  **program_sizes(cfg))
    opt = cfg["optimizer"]
    if opt["type"] != "adam":
        raise ValueError("ouro builder: optimizer %r" % opt["type"])
    with fluid.program_guard(main, startup):
        fluid.optimizer.Adam(
            learning_rate=opt["learning_rate"], beta1=opt["beta1"],
            beta2=opt["beta2"], epsilon=opt["epsilon"]).minimize(loss)
    names = looped_param_names(cfg["num_hidden_layers"])
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
