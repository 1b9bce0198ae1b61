"""Olmo-Hybrid-7B's pipeline stage as a cached decode step Program, from
a configuration file, with what the generation cell makes from the seed
beside it.

The step is the program's own
`paddle_tpu.models.linear_moe_program.build_linear_moe_cached_step_program`
under Olmo-Hybrid's options (a block of tokens in, the next token's
logits out; a sub-layer's output normed; a convolution tail and a
float32 recurrent state of 96 x 192 a head, two heads side by side, a
linear layer through `causal_conv1d` and `gated_delta_rule` with beta in
(0, 2); keys and values over the whole extent a full layer through
`cached_attention`, a key/value head a query head, no rotation; a dense
feed-forward on every layer and no router) at the configuration's
widths; `fluid.ProgramDecoder` scans it.

The weights are drawn as benchmark/models/qwen3next_decode.py draws them
(its `_draw`: pangu's integer sums, exaone's `qk_gain` on the query
norm's scale, the convolution's filter N(0, `conv_std`), `A_log` = log
U(0, 16), `dt_bias` = softplus^-1 of a step log-uniform in [`dt_min`,
`dt_max`]; `root`: a parameter's stream is its name's, so a block can be
made alone for the reference bit for bit as it is served).  `prompts` is
a pure function of the seed.
"""

from benchmark import harness

_lookup = harness.Lookup()
_qwen = _lookup.module("models", "qwen3next_decode")
root = _qwen.root
prompts = _qwen.prompts
ends = _qwen.ends

LINEAR, FULL = "linear_attention", "full_attention"


def layer_types(cfg):
    """The layers this chip serves: the first `num_hidden_layers` of the
    source's `layer_types`."""
    return tuple(cfg["layer_types"][:cfg["num_hidden_layers"]])


def state_pack(cfg):
    """The value heads side by side in a unit of the state the decoder
    carries (the program's choice: kernels/gdn_step.py `state_pack`)."""
    from paddle_tpu.kernels import gdn_step

    return gdn_step.state_pack(cfg["linear_num_value_heads"],
                               cfg["linear_value_head_dim"])


def sizes(cfg):
    """The configuration's keys as the step builder's arguments."""
    if cfg["tie_word_embeddings"] or cfg["attention_bias"] \
            or cfg["hidden_act"] != "silu" \
            or cfg["rope_parameters"]["rope_theta"] is not None \
            or not cfg["linear_allow_neg_eigval"] \
            or cfg["head_dim"] * cfg["num_attention_heads"] \
            != cfg["hidden_size"] \
            or set(layer_types(cfg)) - {LINEAR, FULL} \
            or any((kind == FULL) != ((i + 1)
                                      % cfg["full_attention_interval"] == 0)
                   for i, kind in enumerate(layer_types(cfg))):
        raise ValueError("olmohybrid_decode builder: configuration %r asks "
                         "for what the step does not build" % cfg["name"])
    return dict(
        vocab_size=cfg["vocab_size"], layer_types=layer_types(cfg),
        n_head=cfg["num_attention_heads"],
        n_kv_head=cfg["num_key_value_heads"], d_head=cfg["head_dim"],
        key_heads=cfg["linear_num_key_heads"],
        value_heads=cfg["linear_num_value_heads"],
        key_dim=cfg["linear_key_head_dim"],
        value_dim=cfg["linear_value_head_dim"],
        conv_width=cfg["linear_conv_kernel_dim"],
        d_model=cfg["hidden_size"], n_dense=cfg["num_hidden_layers"],
        d_ff=cfg["intermediate_size"], eps=cfg["rms_norm_eps"],
        rope_theta=None, norm_order="post", qk_norm="whole",
        attn_gate=False, beta_scale=2.0)


def state_shapes(cfg, batch):
    """{feed: (shape, "state" | "tail" | "cache")} of what a call hands
    over beside the position: a linear layer's convolution tail (in the
    weights' type) and recurrent state (float32, `state_pack` heads side
    by side), a full layer's keys and values over `serve_positions` (in
    the serving type)."""
    key_width = cfg["linear_num_key_heads"] * cfg["linear_key_head_dim"]
    heads, value_dim = (cfg["linear_num_value_heads"],
                        cfg["linear_value_head_dim"])
    pack = state_pack(cfg)
    out = {}
    for i, kind in enumerate(layer_types(cfg)):
        if kind == LINEAR:
            out["conv_tail_%d" % i] = (
                (batch, cfg["linear_conv_kernel_dim"] - 1,
                 2 * key_width + heads * value_dim), "tail")
            out["delta_state_%d" % i] = (
                (batch, heads // pack, cfg["linear_key_head_dim"],
                 pack * value_dim), "state")
        else:
            for which in "kv":
                out["%s_cache_%d" % (which, i)] = (
                    (batch, cfg["num_key_value_heads"],
                     cfg["serve_positions"], cfg["head_dim"]), "cache")
    return out


def build(cfg, batch, state_rows=0, **changed):
    """{"main", "logits", "state_pairs", "param_names", "state_shapes",
    "probes"} of the cached step at `batch` rows and the configuration's
    `serve_positions`; `changed` overrides builder arguments.

    "probes" is, per linear layer with `state_rows` > 0, (layer,
    {"state": a state pair}) of what the step only writes and a decoder
    carries out of a call's last step: the first `state_rows` rows of
    the recurrent state the step hands on, the heads apart ([state_rows,
    value heads, key_dim, value_dim], as the reference has it)."""
    import jax
    from paddle_tpu.models.linear_moe_program import (
        build_linear_moe_cached_step_program, linear_moe_param_names)

    args = dict(sizes(cfg), state_rows=state_rows, **changed)
    main, _, logits, pairs, parts = build_linear_moe_cached_step_program(
        batch, cfg["serve_positions"], **args)
    linear = [i for i, kind in enumerate(layer_types(cfg))
              if kind == LINEAR]
    probes = [(i, {"state": ("probe_%d.state" % i, var.name)})
              for i, var in zip(linear, parts["delta_state"])]
    names = linear_moe_param_names(layer_types(cfg), args["n_dense"],
                                   norm_order=args["norm_order"])
    built = {p.name for p in main.global_block().all_parameters()}
    if set(jax.tree_util.tree_leaves(names)) != built:
        raise ValueError("the program's parameters %s are not those "
                         "configuration %r names"
                         % (sorted(built), cfg["name"]))
    return {"main": main, "logits": logits, "state_pairs": pairs,
            "param_names": names, "probes": probes,
            "state_shapes": state_shapes(cfg, batch)}


def _shapes(cfg, layer):
    """{name: (shape, kind)} of one block's parameters; qwen3next's
    kinds."""
    d, f = cfg["hidden_size"], cfg["intermediate_size"]
    out = {"post_attn_norm": ((d,), "norm"), "post_ffn_norm": ((d,), "norm"),
           "ffn_in": ((d, 2 * f), "matrix"), "ffn_out": ((f, d), "matrix")}
    if layer_types(cfg)[layer] == LINEAR:
        heads, value_dim = (cfg["linear_num_value_heads"],
                            cfg["linear_value_head_dim"])
        key_width = cfg["linear_num_key_heads"] * cfg["linear_key_head_dim"]
        value_width = heads * value_dim
        out.update(
            w_qkvz=((d, 2 * key_width + 2 * value_width), "matrix"),
            w_ba=((d, 2 * heads), "matrix"),
            conv=((2 * key_width + value_width,
                   cfg["linear_conv_kernel_dim"]), "conv"),
            a_log=((heads,), "a_log"), dt_bias=((heads,), "dt_bias"),
            out_norm=((value_dim,), "norm"),
            wo=((value_width, d), "matrix"))
    else:
        heads, kv_heads, dim = (cfg["num_attention_heads"],
                                cfg["num_key_value_heads"], cfg["head_dim"])
        out.update(
            wq=((d, heads * dim), "matrix"),
            wk=((d, kv_heads * dim), "matrix"),
            wv=((d, kv_heads * dim), "matrix"),
            q_norm=((heads * dim,), "query_norm"),
            k_norm=((kv_heads * dim,), "norm"),
            wo=((heads * dim, d), "matrix"))
    return out


def block(cfg, spec, key, layer):
    """The parameters of block `layer` from the `root` key.  Pure jax."""
    return {name: _qwen._draw(spec, key, "block_%d.%s" % (layer, name),
                              shape, kind)
            for name, (shape, kind) in _shapes(cfg, layer).items()}


def weights(cfg, spec, key):
    """Every parameter from a seeded key in the type it is served in, as
    the tree benchmark/reference/olmo_hybrid.py documents.  Pure jax:
    call it under one `jax.jit`."""
    key = root(key)
    tree = ends(cfg, spec, key)
    tree["blocks"] = [block(cfg, spec, key, i)
                      for i in range(cfg["num_hidden_layers"])]
    return tree
