"""A GPT-2-shaped decoder as a fluid Program, from a configuration file.

The network is the program's own
`paddle_tpu.models.transformer_program.build_transformer_program` (learned
positions, pre-LayerNorm blocks, fused QKV, the `flash_attention` op, an
untied output head); this file asks for it at the configuration's sizes,
adds the configuration's optimizer, and reads the parameter names off the
op descs in layer order for the plain reference
(benchmark/reference/gpt2.py).
"""

FEED_NAMES = ("tokens", "positions", "targets")


def inner_width(cfg):
    return cfg["n_inner"] or 4 * cfg["n_embd"]


def param_names(program):
    """Parameter names by layer, read from the forward ops in order:
    two embeddings, then per block ln/qkv/proj/ln/fc1/fc2, then the last
    LayerNorm and the head."""
    ops = program.global_block().desc.ops
    emb = [od.input("W")[0] for od in ops if od.type == "lookup_table"]
    norms = [(od.input("Scale")[0], od.input("Bias")[0])
             for od in ops if od.type == "layer_norm"]
    dense = [(od.input("Y")[0], ops[i + 1].input("Y")[0])
             for i, od in enumerate(ops) if od.type == "mul"]
    blocks = []
    for i in range((len(norms) - 1) // 2):
        blocks.append({
            "ln_1": norms[2 * i], "qkv": dense[4 * i],
            "proj": dense[4 * i + 1], "ln_2": norms[2 * i + 1],
            "fc_1": dense[4 * i + 2], "fc_2": dense[4 * i + 3]})
    return {"wte": emb[0], "wpe": emb[1], "blocks": blocks,
            "ln_f": norms[-1], "head": dense[-1]}


def build(cfg, batch, train):
    import paddle_tpu.fluid as fluid
    from paddle_tpu.models.transformer_program import (
        build_transformer_program)

    if not train:
        raise ValueError("gpt2 builder: only the training program exists")
    seq = cfg["n_positions"]
    main, startup, loss, _ = build_transformer_program(
        batch, seq, cfg["vocab_size"], n_layer=cfg["n_layer"],
        n_head=cfg["n_head"], d_model=cfg["n_embd"],
        d_ff=inner_width(cfg), causal=True)
    opt = cfg["optimizer"]
    if opt["type"] != "adam":
        raise ValueError("gpt2 builder: optimizer %r" % opt["type"])
    with fluid.program_guard(main, startup):
        fluid.optimizer.Adam(
            learning_rate=opt["learning_rate"], beta1=opt["beta1"],
            beta2=opt["beta2"], epsilon=opt["epsilon"]).minimize(loss)
    names = param_names(main)
    if len(names["blocks"]) != cfg["n_layer"]:
        raise ValueError("the program's transformer does not have the "
                         "%d blocks configuration %r states"
                         % (cfg["n_layer"], cfg["name"]))
    return {"main": main, "startup": startup,
            "feed_names": list(FEED_NAMES), "fetch": loss,
            "param_names": names, "items_per_step": batch * seq}


def sample(cfg, batch, key):
    """One seeded batch (pure jax): uniform tokens, each position's
    target the token that follows it."""
    import jax
    import jax.numpy as jnp

    seq = cfg["n_positions"]
    text = jax.random.randint(key, (batch, seq + 1), 0,
                              cfg["vocab_size"], jnp.int32)
    return {
        "tokens": text[:, :-1],
        "positions": jnp.broadcast_to(
            jnp.arange(seq, dtype=jnp.int32), (batch, seq)),
        "targets": text[:, 1:, None],
    }
