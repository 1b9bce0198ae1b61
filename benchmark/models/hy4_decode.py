"""One chip's share of Hy4-preview as a cached decode step Program, from a
configuration file, with what the reuse cell makes from the seed beside
it.

The step is the program's own
`paddle_tpu.models.latent_moe_program.build_latent_moe_cached_step_program`
with the options this block asks for (pre-norm, the indexer on the layers
`indexer_types` calls full and its set inherited on the others, the
residual's four streams, the gate, the sink, the clamp, the float32
head, the router's selection bias and no groups, no YaRN) at the
configuration's widths; `fluid.ProgramDecoder` scans it.

The weights are drawn as benchmark/models/dsv32_decode.py draws them
(pangu_decode's `_draw`, `root`: a parameter's stream is its name's, so a
block can be made alone for the reference bit for bit as it is served)
with three kinds more, all float32 whatever the served type: a
hyper-connection's projections N(0, `hc_std`), its scalars 1 + N(0, std)
as a norm's scale is drawn, and a sink, `sink_mean` + N(0, `bias_std`).
`documents` and `prompts` (the questions) are pure functions of the seed.
"""

from benchmark import harness

_dsv32 = harness.Lookup().module("models", "dsv32_decode")
root = _dsv32.root
documents = _dsv32.documents
prompts = _dsv32.prompts
ends = _dsv32.ends


def layer_kinds(cfg):
    """(`indexer_types`, `mlp_layer_types`) of the layers served: the
    published lists up to `num_hidden_layers`."""
    layers = cfg["num_hidden_layers"]
    return cfg["indexer_types"][:layers], cfg["mlp_layer_types"][:layers]


def sizes(cfg):
    """The configuration's keys as the step builder's arguments."""
    chooser, mlp = layer_kinds(cfg)
    dense = mlp.count("dense")
    if cfg["n_shared_experts"] != 1 or cfg["attention_bias"] \
            or cfg["tie_word_embeddings"] or cfg["num_nextn_predict_layers"] \
            or cfg["n_group"] != 1 or cfg["topk_group"] != 1 \
            or cfg["hidden_act"] != "silu" or not cfg["use_mla"] \
            or not cfg["use_dsa"] or not cfg["gated_mla"] \
            or cfg["gating_type"] != "elementwise" \
            or not cfg["learnable_sink"] or not cfg["enable_ihc"] \
            or not cfg["enable_lm_head_fp32"] \
            or cfg["rope_parameters"]["rope_type"] != "default" \
            or cfg["qk_head_dim"] != cfg["qk_nope_head_dim"] \
            + cfg["qk_rope_head_dim"] \
            or mlp != ["dense"] * dense + ["sparse"] * (len(mlp) - dense) \
            or set(cfg["layer_types"]) != {"deepseek_sparse_attention"}:
        raise ValueError("hy4_decode builder: configuration %r asks for "
                         "what the step does not build" % cfg["name"])
    return dict(
        vocab_size=cfg["vocab_size"], n_layer=cfg["num_hidden_layers"],
        n_dense=dense, n_head=cfg["num_attention_heads"],
        d_model=cfg["hidden_size"], q_rank=cfg["q_lora_rank"],
        kv_rank=cfg["kv_lora_rank"], d_nope=cfg["qk_nope_head_dim"],
        d_rope=cfg["qk_rope_head_dim"], d_v=cfg["v_head_dim"],
        d_ff=cfg["intermediate_size"],
        d_expert=cfg["moe_intermediate_size"],
        n_experts=cfg["scored_experts"],
        held=(cfg["first_expert"], cfg["n_routed_experts"]),
        top_k=cfg["num_experts_per_tok"], norm_topk=cfg["norm_topk_prob"],
        routed_scale=cfg["routed_scaling_factor"], eps=cfg["rms_norm_eps"],
        rope_theta=float(cfg["rope_parameters"]["rope_theta"]),
        sandwich_norm=False,
        indexer=(cfg["index_n_heads"], cfg["index_head_dim"],
                 cfg["index_topk"]),
        indexer_types=chooser, router_bias=True,
        hc={"streams": cfg["hc_mult"], "eps": cfg["hc_eps"],
            "magnitude": float(cfg["hc_magnitude"]),
            "iterations": cfg["hc_sinkhorn_iterations"]},
        gated=True, sink=True, swiglu_limit=float(cfg["swiglu_limit"]),
        head_float32=True)


def build(cfg, batch, **changed):
    """{"main", "logits", "state_pairs", "param_names", "cache_shapes",
    "probes"} of the cached step at `batch` rows and the configuration's
    `serve_positions`; `changed` overrides builder arguments (a control
    of `correct` serves a step without the gate, or another `hc`).

    "cache_shapes" is {feed: shape} of the step's caches: latents a
    layer, index keys on the layers that choose.  "probes" is, per layer,
    (layer, {what: a state pair}) of what the step only writes and a
    decoder carries out of a call's last step: "attn_in" [batch, 1,
    hidden] the attention sub-layer's normed input, "selected" [batch,
    index_topk] the slots its attention read (on a layer that does not
    choose, the Variable of the layer it inherits from, carried once
    more), "attn_out" [batch, 1, hidden] what the sub-layer gave for
    them, "streams_in" and "streams_out" [batch, 1, streams, hidden] the
    residual before the sub-layer's hyper-connection and after it; and
    for an expert layer pangu's three, "in", "idx", "out"."""
    from paddle_tpu.models.latent_moe_program import (
        build_latent_moe_cached_step_program, latent_moe_param_names)

    positions = cfg["serve_positions"]
    options = dict(sizes(cfg), **changed)
    main, _, logits, pairs, parts = build_latent_moe_cached_step_program(
        batch, positions, **options)
    layers, dense = options["n_layer"], options["n_dense"]
    probes = []
    for i in range(layers):
        found = {what: parts[what][i] for what in (
            "attn_in", "selected", "attn_out", "streams_in", "streams_out")}
        if i >= dense:
            found.update({what: parts[part][i - dense] for what, part in (
                ("in", "moe_in"), ("idx", "top_idx"), ("out", "moe_out"))})
        probes.append((i, {what: ("probe_%d.%s" % (i, what), var.name)
                           for what, var in found.items()}))
    names = latent_moe_param_names(
        layers, dense, sandwich_norm=False, indexer=True, router_bias=True,
        indexer_types=options["indexer_types"], hc=True,
        gated=options["gated"], sink=options["sink"])
    built = {p.name for p in main.global_block().all_parameters()}
    named = {name for block in names["blocks"] for name in block.values()} \
        | {names[k] for k in ("embed", "norm_f", "head")}
    if named != built:
        raise ValueError("the program's parameters %s are not those "
                         "configuration %r names"
                         % (sorted(built ^ named), cfg["name"]))
    width = cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"]
    shapes = {}
    for feed, _ in pairs:
        if feed.startswith("latent_cache_"):
            shapes[feed] = (batch, positions, width)
        elif feed.startswith("index_cache_"):
            shapes[feed] = (batch, positions, cfg["index_head_dim"])
    return {"main": main, "logits": logits, "state_pairs": pairs,
            "param_names": names, "probes": probes, "cache_shapes": shapes}


def _shapes(cfg, layer):
    """{name: (shape, kind)} of one block's parameters; dsv32's kinds and
    "hc_matrix", "hc_scalar", "hc_bias" and "sink" (all float32)."""
    d, heads = cfg["hidden_size"], cfg["num_attention_heads"]
    q, kv = cfg["q_lora_rank"], cfg["kv_lora_rank"]
    nope, rope, v = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                     cfg["v_head_dim"])
    ih, idim = cfg["index_n_heads"], cfg["index_head_dim"]
    n = cfg["hc_mult"]
    chooser, mlp = layer_kinds(cfg)
    out = {
        "input_norm": ((d,), "norm"), "w_dq": ((d, q), "matrix"),
        "q_norm": ((q,), "norm"),
        "w_uq_nope": ((q, heads * nope), "query"),
        "w_uq_rope": ((q, heads * rope), "query"),
        "w_dkv": ((d, kv + rope), "matrix"), "kv_norm": ((kv,), "norm"),
        "w_uk": ((kv, heads * nope), "matrix"),
        "w_uv": ((kv, heads * v), "matrix"),
        "w_g": ((d, heads * v), "matrix"), "sink": ((heads,), "sink"),
        "wo": ((heads * v, d), "matrix"),
        "pre_mlp_norm": ((d,), "norm"),
    }
    for sub in ("attn", "mlp"):
        out.update({"hc_%s_p" % sub: ((n * d, n * n + 2 * n), "hc_matrix"),
                    "hc_%s_a" % sub: ((3,), "hc_scalar"),
                    "hc_%s_b" % sub: ((n * n + 2 * n,), "hc_bias")})
    if chooser[layer] == "full":
        out.update(w_iq=((q, ih * idim), "index_query"),
                   w_ik=((d, idim), "matrix"), ik_norm=((idim,), "norm"),
                   ik_norm_b=((idim,), "bias"), w_iw=((d, ih), "matrix"))
    if mlp[layer] == "dense":
        f = cfg["intermediate_size"]
        out.update(ffn_in=((d, 2 * f), "matrix"), ffn_out=((f, d), "matrix"))
    else:
        f, held = cfg["moe_intermediate_size"], cfg["n_routed_experts"]
        scored = cfg["scored_experts"]
        out.update(
            shared_in=((d, 2 * f), "matrix"), shared_out=((f, d), "matrix"),
            router=((d, scored), "matrix"),
            router_bias=((scored,), "bias"),
            w_gate=((held, d, f), "routed"), w_up=((held, d, f), "routed"),
            w_down=((held, f, d), "routed"))
    return out


def _draw(spec, key, name, shape, kind):
    """dsv32_decode's `_draw` for its kinds; a hyper-connection's
    parameters and a sink are float32 whatever the served type: the
    projections N(0, hc_std), the scalars as a norm's scale, the biases as
    any bias, a sink `sink_mean` + a bias's draw."""
    f32 = dict(spec, dtype="float32")
    if kind == "hc_matrix":
        return _dsv32._draw(dict(f32, std=spec["hc_std"]), key, name, shape,
                            "matrix")
    if kind == "hc_scalar":
        return _dsv32._draw(f32, key, name, shape, "norm")
    if kind == "hc_bias":
        return _dsv32._draw(f32, key, name, shape, "bias")
    if kind == "sink":
        return _dsv32._draw(f32, key, name, shape, "bias") \
            + spec["sink_mean"]
    return _dsv32._draw(spec, key, name, shape, kind)


def block(cfg, spec, key, layer):
    """The parameters of block `layer` from the `root` key.  Pure jax."""
    return {name: _draw(spec, key, "block_%d.%s" % (layer, name), shape,
                        kind)
            for name, (shape, kind) in _shapes(cfg, layer).items()}


def weights(cfg, spec, key):
    """Every parameter from a seeded key in the type it is served in, as
    the tree benchmark/reference/hy4_preview.py documents.  Pure jax:
    call it under one `jax.jit`."""
    key = root(key)
    tree = ends(cfg, spec, key)
    tree["blocks"] = [block(cfg, spec, key, i)
                      for i in range(cfg["num_hidden_layers"])]
    return tree
