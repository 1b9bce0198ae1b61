"""SmallThinker-21BA3B's window/full mixture-of-experts decoder as a
fluid training Program, from a configuration file.

The network is the program's own
`paddle_tpu.models.smallthinker_program.build_smallthinker_program` (per
layer of `rope_layout` / `sliding_window_layout` a rotated or unrotated,
windowed or full grouped-query attention through the `flash_attention`
op, and a routed layer of ReGLU experts whose router reads the layer's
input norm; one chip's share: `moe_num_primary_experts` held of the
`scored_experts` scored, from `first_expert` on, and the configuration's
slice of the vocabulary); this file asks for it at the configuration's
sizes, adds the configuration's optimizer, and hands the plain reference
(benchmark/reference/smallthinker.py) the parameters' names in its
layout.

The weights are `--seed`'s draw, as every other training cell's
(benchmark/training.py seeds the start-up program), but for the
embedding, which the configuration draws N(0, `embedding_std`): under
the stack's default (Xavier over `[vocab, hidden]`, 0.0096 an entry)
every router past the first full layer reads nearly the same vector for
every token and a held range's load follows the draw (2,975 to 24,444 of
a layer's 98,304 assignments, PERF.md section 6, PR 48), where a
token's own N(0, 1) vector spreads them evenly (11,886 to 12,650).
"""

FEED_NAMES = ("tokens", "positions", "targets")


def program_sizes(cfg):
    """The configuration's keys as `build_smallthinker_program`'s
    arguments."""
    layers = cfg["num_hidden_layers"]
    if len(cfg["rope_layout"]) != layers \
            or len(cfg["sliding_window_layout"]) != layers:
        raise ValueError("smallthinker builder: rope_layout and "
                         "sliding_window_layout name %d and %d layers, "
                         "num_hidden_layers %d"
                         % (len(cfg["rope_layout"]),
                            len(cfg["sliding_window_layout"]), layers))
    if not cfg["moe_primary_router_apply_softmax"] \
            or not cfg["norm_topk_prob"] or cfg["tie_word_embeddings"] \
            or cfg["rope_scaling"] is not None \
            or cfg["hidden_act"] != "relu" \
            or cfg["router_reads"] != "input_layernorm":
        raise ValueError("smallthinker builder: a router without softmax "
                         "or renormalising, a tied head, scaled rotary "
                         "positions, experts that are not ReGLU and a "
                         "router that reads the experts' input are not "
                         "built")
    return dict(
        seq_len=cfg["sequence_length"], vocab_size=cfg["vocab_size"],
        rope_layout=tuple(cfg["rope_layout"]),
        window_layout=tuple(cfg["sliding_window_layout"]),
        window=cfg["sliding_window_size"],
        n_head=cfg["num_attention_heads"],
        n_kv_head=cfg["num_key_value_heads"], d_model=cfg["hidden_size"],
        d_head=cfg["head_dim"], d_expert=cfg["moe_ffn_hidden_size"],
        n_experts=cfg["scored_experts"],
        top_k=cfg["moe_num_active_primary_experts"],
        held=(cfg["first_expert"], cfg["moe_num_primary_experts"]),
        eps=cfg["rms_norm_eps"], rope_theta=cfg["rope_theta"],
        embed_std=cfg["embedding_std"])


def build(cfg, batch, train):
    import jax
    import paddle_tpu.fluid as fluid
    from paddle_tpu.models.smallthinker_program import (
        build_smallthinker_program, smallthinker_param_names)

    if not train:
        raise ValueError("smallthinker builder: only the training program "
                         "exists")
    seq = cfg["sequence_length"]
    main, startup, loss, _ = build_smallthinker_program(
        batch, **program_sizes(cfg))
    opt = cfg["optimizer"]
    if opt["type"] != "adam":
        raise ValueError("smallthinker builder: optimizer %r" % opt["type"])
    with fluid.program_guard(main, startup):
        fluid.optimizer.Adam(
            learning_rate=opt["learning_rate"], beta1=opt["beta1"],
            beta2=opt["beta2"], epsilon=opt["epsilon"]).minimize(loss)
    names = smallthinker_param_names(cfg["num_hidden_layers"])
    built = {p.name for p in main.global_block().all_parameters()}
    if set(jax.tree_util.tree_leaves(names)) != built:
        raise ValueError("the program's parameters %s are not those "
                         "configuration %r names"
                         % (sorted(built), cfg["name"]))
    return {"main": main, "startup": startup,
            "feed_names": list(FEED_NAMES), "fetch": loss,
            "param_names": names, "items_per_step": batch * seq}


def sample(cfg, batch, key):
    """One seeded batch (pure jax): tokens uniform over the held slice
    of the vocabulary at positions 0..seq-1, each position's target the
    token that follows it."""
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
