"""One chip's share of Qwen3-Next-80B-A3B as a cached decode step
Program, from a configuration file, with what the generation cell makes
from the seed beside it.

The step is the program's own
`paddle_tpu.models.linear_moe_program.build_linear_moe_cached_step_program`
(a block of tokens in, the next token's logits over the held vocabulary
out; a convolution tail and a float32 recurrent state a linear layer
through `causal_conv1d` and `gated_delta_rule`, keys and values over the
whole extent a full layer through `cached_attention`; the held range of
the routed experts through `moe_experts`) at the configuration's widths;
`fluid.ProgramDecoder` scans it.

The weights are drawn as benchmark/models/pangu_decode.py draws them
(its `_draw`, `root`: a parameter's stream is its name's, so a block can
be made alone for the reference bit for bit as it is served) and, for
the queries' per-head norm scale, as benchmark/models/exaone_decode.py
does (`qk_gain`), with three kinds more: the convolution's filter,
N(0, `conv_std`); `A_log` = log U(0, 16) and `dt_bias` = softplus^-1 of a
step drawn log-uniformly from [`dt_min`, `dt_max`], both float32 (the
gates are float32 in the program).  `prompts` is a pure function of the
seed.
"""

import math

from benchmark import harness

_lookup = harness.Lookup()
_pangu = _lookup.module("models", "pangu_decode")
_exaone = _lookup.module("models", "exaone_decode")
root = _pangu.root
prompts = _pangu.prompts
ends = _pangu.ends

LINEAR, FULL = "linear_attention", "full_attention"


def layer_types(cfg):
    return tuple(FULL if (i + 1) % cfg["full_attention_interval"] == 0
                 else LINEAR for i in range(cfg["num_hidden_layers"]))


def sizes(cfg):
    """The configuration's keys as the step builder's arguments."""
    if cfg["decoder_sparse_step"] != 1 or cfg["mlp_only_layers"] \
            or cfg["tie_word_embeddings"] or cfg["use_sliding_window"] \
            or cfg["rope_scaling"] is not None \
            or cfg["hidden_act"] != "silu" \
            or cfg["shared_expert_intermediate_size"] \
            != cfg["moe_intermediate_size"]:
        raise ValueError("qwen3next_decode builder: configuration %r asks "
                         "for what the step does not build" % cfg["name"])
    return dict(
        vocab_size=cfg["vocab_size"], layer_types=layer_types(cfg),
        n_head=cfg["num_attention_heads"],
        n_kv_head=cfg["num_key_value_heads"], d_head=cfg["head_dim"],
        rotary_dim=int(cfg["head_dim"] * cfg["partial_rotary_factor"]),
        key_heads=cfg["linear_num_key_heads"],
        value_heads=cfg["linear_num_value_heads"],
        key_dim=cfg["linear_key_head_dim"],
        value_dim=cfg["linear_value_head_dim"],
        conv_width=cfg["linear_conv_kernel_dim"],
        d_model=cfg["hidden_size"], d_expert=cfg["moe_intermediate_size"],
        n_experts=cfg["scored_experts"],
        held=(cfg["first_expert"], cfg["num_experts"]),
        top_k=cfg["num_experts_per_tok"], norm_topk=cfg["norm_topk_prob"],
        eps=cfg["rms_norm_eps"], rope_theta=float(cfg["rope_theta"]))


def state_shapes(cfg, batch):
    """{feed: (shape, "state" | "tail" | "cache")} of what a call hands
    over beside the position: a linear layer's convolution tail (in the
    weights' type) and recurrent state (float32), a full layer's keys
    and values over `serve_positions` (in the serving type)."""
    key_width = cfg["linear_num_key_heads"] * cfg["linear_key_head_dim"]
    heads, value_dim = (cfg["linear_num_value_heads"],
                        cfg["linear_value_head_dim"])
    out = {}
    for i, kind in enumerate(layer_types(cfg)):
        if kind == LINEAR:
            out["conv_tail_%d" % i] = (
                (batch, cfg["linear_conv_kernel_dim"] - 1,
                 2 * key_width + heads * value_dim), "tail")
            out["delta_state_%d" % i] = (
                (batch, heads, cfg["linear_key_head_dim"], value_dim),
                "state")
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

    "probes" is, per layer, (layer, {what: a state pair}) of what the
    step only writes and a decoder carries out of a call's last step:
    pangu's three of the expert layer, "in", "idx", "out", and for a
    linear layer with `state_rows` > 0 "state", the first `state_rows`
    rows of the recurrent state the step hands on."""
    import jax
    from paddle_tpu.models.linear_moe_program import (
        build_linear_moe_cached_step_program, linear_moe_param_names)

    args = dict(sizes(cfg), state_rows=state_rows, **changed)
    main, _, logits, pairs, parts = build_linear_moe_cached_step_program(
        batch, cfg["serve_positions"], **args)
    probes, linear = [], 0
    for i, kind in enumerate(layer_types(cfg)):
        found = {what: parts[part][i] for what, part in (
            ("in", "moe_in"), ("idx", "top_idx"), ("out", "moe_out"))}
        if kind == LINEAR:
            if state_rows:
                found["state"] = parts["delta_state"][linear]
            linear += 1
        probes.append((i, {what: ("probe_%d.%s" % (i, what), var.name)
                           for what, var in found.items()}))
    names = linear_moe_param_names(layer_types(cfg))
    built = {p.name for p in main.global_block().all_parameters()}
    if set(jax.tree_util.tree_leaves(names)) != built:
        raise ValueError("the program's parameters %s are not those "
                         "configuration %r names"
                         % (sorted(built), cfg["name"]))
    return {"main": main, "logits": logits, "state_pairs": pairs,
            "param_names": names, "probes": probes,
            "state_shapes": state_shapes(cfg, batch)}


def _shapes(cfg, layer):
    """{name: (shape, kind)} of one block's parameters; pangu's and
    exaone's kinds and "conv", "a_log", "dt_bias"."""
    d = cfg["hidden_size"]
    f, held = cfg["moe_intermediate_size"], cfg["num_experts"]
    out = {
        "input_norm": ((d,), "norm"), "pre_mlp_norm": ((d,), "norm"),
        "shared_in": ((d, 2 * f), "matrix"),
        "shared_out": ((f, d), "matrix"),
        "shared_gate": ((d, 1), "matrix"),
        "router": ((d, cfg["scored_experts"]), "matrix"),
        "w_gate": ((held, d, f), "routed"), "w_up": ((held, d, f), "routed"),
        "w_down": ((held, f, d), "routed"),
    }
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
            wq=((d, 2 * heads * dim), "matrix"),
            wk=((d, kv_heads * dim), "matrix"),
            wv=((d, kv_heads * dim), "matrix"),
            q_norm=((dim,), "query_norm"), k_norm=((dim,), "norm"),
            wo=((heads * dim, d), "matrix"))
    return out


def _draw(spec, key, name, shape, kind):
    """pangu_decode's and exaone_decode's `_draw` for their kinds; the
    convolution's filter N(0, conv_std) in the weights' type; `A_log` =
    log U(2^-10, 16) and `dt_bias` = log(expm1(dt)), dt log-uniform in
    [dt_min, dt_max], float32 both (an ulp of a float32 scalar between
    two compilations is seven orders below what `correct` reads)."""
    import zlib

    import jax
    import jax.numpy as jnp

    if kind == "conv":
        return _pangu._draw(dict(spec, std=spec["conv_std"]), key, name,
                            shape, "matrix")
    if kind not in ("a_log", "dt_bias"):
        return _exaone._draw(spec, key, name, shape, kind)
    drawn = jax.random.uniform(
        jax.random.fold_in(key, zlib.crc32(name.encode()) & 0x7FFFFFFF),
        shape, jnp.float32)
    if kind == "a_log":
        return jnp.log(2.0 ** -10 + (16.0 - 2.0 ** -10) * drawn)
    low, high = math.log(spec["dt_min"]), math.log(spec["dt_max"])
    dt = jnp.exp(low + (high - low) * drawn)
    return dt + jnp.log(-jnp.expm1(-dt))


def block(cfg, spec, key, layer):
    """The parameters of block `layer` from the `root` key.  Pure jax."""
    return {name: _draw(spec, key, "block_%d.%s" % (layer, name), shape,
                        kind)
            for name, (shape, kind) in _shapes(cfg, layer).items()}


def weights(cfg, spec, key):
    """Every parameter from a seeded key in the type it is served in, as
    the tree benchmark/reference/qwen3_next.py documents.  Pure jax:
    call it under one `jax.jit`."""
    key = root(key)
    tree = ends(cfg, spec, key)
    tree["blocks"] = [block(cfg, spec, key, i)
                      for i in range(cfg["num_hidden_layers"])]
    return tree
