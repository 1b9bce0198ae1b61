"""One chip's share of Ling-3.0-flash as a cached decode step Program,
from a configuration file, with what the generation cell makes from the
seed beside it.

The step is the program's own
`paddle_tpu.models.linear_moe_program.build_linear_moe_cached_step_program`
under Ling-3.0-flash's options (a block of tokens in, the next token's
logits over the held vocabulary out; **three kinds of state**: a
convolution tail and a float32 recurrent state a KDA layer through
`causal_conv1d` and `gated_delta_rule` under a gate a key channel, a
cache of latents the latent-attention layer through
`mla_cached_attention`; the held range of the routed experts through
`moe_experts`) at the configuration's widths; `fluid.ProgramDecoder`
scans it.

The weights are drawn as benchmark/models/pangu_decode.py draws them
(its `_draw`, `root`, `q_gain` on the queries' projections: a
parameter's stream is its name's, so a block can be made alone for the
reference bit for bit as it is served), dsv32_decode.py's selection bias
N(0, `bias_std`) in float32, qwen3next_decode.py's convolution filter
N(0, `conv_std`), and three kinds of this model's own: the latent's
down-projection W_dkv N(0, `kv_gain` std), so that the latent's norm
does something (under N(0, std) a latent's root mean square is 1.01 at
this hidden size, and a norm left out would change nothing), and the
gate's two, float32 both (the gates are float32 in the program):
`A_log` uniform in [log `rate_min`, log `rate_max`] a head and `dt_bias`
uniform in [`bias_min`, `bias_max`] a head and key channel, so that g =
-5 sigmoid(exp(A_log) (f + dt_bias)) spreads a head's 128 decays from a
position's memory to hundreds (the workload's `weights.why`).  `prompts`
is a pure function of the seed.
"""

from benchmark import harness

_lookup = harness.Lookup()
_pangu = _lookup.module("models", "pangu_decode")
_dsv32 = _lookup.module("models", "dsv32_decode")
root = _pangu.root
prompts = _pangu.prompts
ends = _pangu.ends

KDA, LATENT = "linear_attention", "latent_attention"


def layer_types(cfg):
    return tuple(LATENT if (i + 1) % cfg["layer_group_size"] == 0 else KDA
                 for i in range(cfg["num_hidden_layers"]))


def sizes(cfg):
    """The configuration's keys as the step builder's arguments."""
    if cfg["num_shared_experts"] != 1 or cfg["tie_word_embeddings"] \
            or cfg["num_nextn_predict_layers"] or cfg["q_lora_rank"] \
            or cfg["rope_scaling"] is not None \
            or cfg["hidden_act"] != "silu" \
            or cfg["score_function"] != "sigmoid" \
            or cfg["topk_method"] != "noaux_tc" \
            or cfg["num_kv_heads_for_linear_attn"] or cfg["use_kda_lora"] \
            or not (cfg["kda_safe_gate"] and cfg["linear_silu"]
                    and cfg["moe_router_enable_expert_bias"]) \
            or cfg["group_norm_size"] != 1 or cfg["use_bias"] \
            or cfg["gated_attention_proj_granularity_type"] != "head_wise" \
            or cfg["moe_shared_expert_intermediate_size"] \
            != cfg["moe_intermediate_size"] \
            or any(cfg[k][:cfg["num_hidden_layers"]] != [0] * cfg[
                "num_hidden_layers"] for k in (
                    "expert_swiglu_limit_list",
                    "share_expert_swiglu_limit_list")):
        raise ValueError("ling3_decode builder: configuration %r asks for "
                         "what the step does not build" % cfg["name"])
    heads, dim = cfg["num_attention_heads"], cfg["head_dim"]
    return dict(
        vocab_size=cfg["vocab_size"], layer_types=layer_types(cfg),
        gate="channel", gate_floor=float(cfg["kda_lower_bound"]),
        n_head=heads, key_heads=heads, value_heads=heads, key_dim=dim,
        value_dim=dim, conv_width=cfg["short_conv_kernel_size"],
        kv_rank=cfg["kv_lora_rank"], d_nope=cfg["qk_nope_head_dim"],
        d_rope=cfg["qk_rope_head_dim"], d_v=cfg["v_head_dim"],
        d_model=cfg["hidden_size"], n_dense=cfg["first_k_dense_replace"],
        d_ff=cfg["intermediate_size"],
        d_expert=cfg["moe_intermediate_size"],
        n_experts=cfg["scored_experts"],
        held=(cfg["first_expert"], cfg["num_experts"]),
        top_k=cfg["num_experts_per_tok"], norm_topk=cfg["norm_topk_prob"],
        scoring="sigmoid", shared_gate=False,
        routed_scale=cfg["routed_scaling_factor"], router_bias=True,
        n_group=cfg["n_group"], topk_group=cfg["topk_group"],
        eps=cfg["rms_norm_eps"], rope_theta=float(cfg["rope_theta"]))


def state_shapes(cfg, batch):
    """{feed: (shape, "state" | "tail" | "cache")} of what a call hands
    over beside the position: a KDA layer's convolution tail (in the
    weights' type) and recurrent state (float32), the latent layer's
    cache over `serve_positions` (in the serving type)."""
    heads, dim = cfg["num_attention_heads"], cfg["head_dim"]
    out = {}
    for i, kind in enumerate(layer_types(cfg)):
        if kind == KDA:
            out["conv_tail_%d" % i] = (
                (batch, cfg["short_conv_kernel_size"] - 1, 3 * heads * dim),
                "tail")
            out["delta_state_%d" % i] = ((batch, heads, dim, dim), "state")
        else:
            out["latent_cache_%d" % i] = (
                (batch, cfg["serve_positions"],
                 cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"]), "cache")
    return out


def build(cfg, batch, state_rows=0, **changed):
    """{"main", "logits", "state_pairs", "param_names", "state_shapes",
    "probes"} of the cached step at `batch` rows and the configuration's
    `serve_positions`; `changed` overrides builder arguments.

    "probes" is, per layer, (layer, {what: a state pair}) of what the
    step only writes and a decoder carries out of a call's last step:
    for an expert layer pangu's three, "in", "idx", "out", and for a KDA
    layer with `state_rows` > 0 "state", the first `state_rows` rows of
    the recurrent state the step hands on."""
    import jax
    from paddle_tpu.models.linear_moe_program import (
        build_linear_moe_cached_step_program, linear_moe_param_names)

    args = dict(sizes(cfg), state_rows=state_rows, **changed)
    main, _, logits, pairs, parts = build_linear_moe_cached_step_program(
        batch, cfg["serve_positions"], **args)
    dense = cfg["first_k_dense_replace"]
    probes, linear = [], 0
    for i, kind in enumerate(layer_types(cfg)):
        found = {} if i < dense else {
            what: parts[part][i - dense] for what, part in (
                ("in", "moe_in"), ("idx", "top_idx"), ("out", "moe_out"))}
        if kind == KDA:
            if state_rows:
                found["state"] = parts["delta_state"][linear]
            linear += 1
        probes.append((i, {what: ("probe_%d.%s" % (i, what), var.name)
                           for what, var in found.items()}))
    names = linear_moe_param_names(layer_types(cfg), dense, "channel",
                                   shared_gate=False, router_bias=True)
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
    dsv32's kinds and "conv", "a_log", "dt_bias"."""
    d, heads, dim = (cfg["hidden_size"], cfg["num_attention_heads"],
                     cfg["head_dim"])
    out = {"input_norm": ((d,), "norm"), "pre_mlp_norm": ((d,), "norm")}
    if layer < cfg["first_k_dense_replace"]:
        f = cfg["intermediate_size"]
        out.update(ffn_in=((d, 2 * f), "matrix"), ffn_out=((f, d), "matrix"))
    else:
        f, held = cfg["moe_intermediate_size"], cfg["num_experts"]
        scored = cfg["scored_experts"]
        out.update(
            shared_in=((d, 2 * f), "matrix"), shared_out=((f, d), "matrix"),
            router=((d, scored), "matrix"),
            router_bias=((scored,), "bias"),
            w_gate=((held, d, f), "routed"), w_up=((held, d, f), "routed"),
            w_down=((held, f, d), "routed"))
    if layer_types(cfg)[layer] == KDA:
        width = heads * dim
        out.update(
            w_qkvf=((d, 4 * width), "matrix"),
            w_bz=((d, 2 * heads), "matrix"),
            conv=((3 * width, cfg["short_conv_kernel_size"]), "conv"),
            a_log=((heads,), "a_log"), dt_bias=((width,), "dt_bias"),
            out_norm=((dim,), "norm"), wo=((width, d), "matrix"))
    else:
        kv, nope, rope, v = (cfg["kv_lora_rank"], cfg["qk_nope_head_dim"],
                             cfg["qk_rope_head_dim"], cfg["v_head_dim"])
        out.update(
            wq_nope=((d, heads * nope), "query"),
            wq_rope=((d, heads * rope), "query"),
            w_dkv=((d, kv + rope), "latent"), kv_norm=((kv,), "norm"),
            w_uk=((kv, heads * nope), "matrix"),
            w_uv=((kv, heads * v), "matrix"),
            w_z=((d, heads), "matrix"), wo=((heads * v, d), "matrix"))
    return out


def _draw(spec, key, name, shape, kind):
    """pangu_decode's and dsv32_decode's `_draw` for their kinds; the
    convolution's filter N(0, conv_std) and the latent's down-projection
    N(0, kv_gain std) in the weights' type; `A_log` uniform in [log
    rate_min, log rate_max] and `dt_bias` uniform in [bias_min,
    bias_max], float32 both (an ulp of a float32 scalar between two
    compilations is seven orders below what `correct` reads)."""
    import math
    import zlib

    import jax
    import jax.numpy as jnp

    if kind == "conv":
        return _pangu._draw(dict(spec, std=spec["conv_std"]), key, name,
                            shape, "matrix")
    if kind == "latent":    # (pangu's "query" kind: std times a gain)
        return _pangu._draw(dict(spec, q_gain=spec["kv_gain"]), key, name,
                            shape, "query")
    if kind not in ("a_log", "dt_bias"):
        return _dsv32._draw(spec, key, name, shape, kind)
    drawn = jax.random.uniform(
        jax.random.fold_in(key, zlib.crc32(name.encode()) & 0x7FFFFFFF),
        shape, jnp.float32)
    if kind == "a_log":
        low, high = math.log(spec["rate_min"]), math.log(spec["rate_max"])
        return low + (high - low) * drawn
    return spec["bias_min"] + (spec["bias_max"] - spec["bias_min"]) * drawn


def block(cfg, spec, key, layer):
    """The parameters of block `layer` from the `root` key.  Pure jax."""
    return {name: _draw(spec, key, "block_%d.%s" % (layer, name), shape,
                        kind)
            for name, (shape, kind) in _shapes(cfg, layer).items()}


def weights(cfg, spec, key):
    """Every parameter from a seeded key in the type it is served in, as
    the tree benchmark/reference/ling3_flash.py documents.  Pure jax:
    call it under one `jax.jit`."""
    key = root(key)
    tree = ends(cfg, spec, key)
    tree["blocks"] = [block(cfg, spec, key, i)
                      for i in range(cfg["num_hidden_layers"])]
    return tree
