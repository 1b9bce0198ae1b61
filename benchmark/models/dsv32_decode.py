"""One chip's share of DeepSeek-V3.2 as a cached decode step Program,
from a configuration file, with what the session cell makes from the
seed beside it.

The step is the program's own
`paddle_tpu.models.latent_moe_program.build_latent_moe_cached_step_program`
with the options this block asks for (pre-norm, the lightning indexer
and its key cache, the router's groups and selection bias, YaRN) at the
configuration's widths; `fluid.ProgramDecoder` scans it.

The weights are drawn as benchmark/models/pangu_decode.py draws them
(its `_draw`, `root`: a parameter's stream is its name's, so a block can
be made alone for the reference bit for bit as it is served), with two
kinds more: a bias, N(0, `bias_std`) around 0 in float32 (the router's
selection bias and the index key's LayerNorm bias), and the index
queries' matrix, which `qi_gain` multiplies.  `documents` and `prompts`
(the questions) are pure functions of the seed.
"""

from benchmark import harness

_pangu = harness.Lookup().module("models", "pangu_decode")
root = _pangu.root


def sizes(cfg):
    """The configuration's keys as the step builder's arguments."""
    scaling = cfg["rope_scaling"]
    if cfg["num_key_value_heads"] != cfg["num_attention_heads"] \
            or cfg["n_shared_experts"] != 1 or cfg["attention_bias"] \
            or cfg["tie_word_embeddings"] or cfg["num_nextn_predict_layers"] \
            or cfg["scoring_func"] != "sigmoid" \
            or cfg["topk_method"] != "noaux_tc" \
            or scaling["type"] != "yarn" \
            or scaling["mscale"] != scaling["mscale_all_dim"] \
            or cfg["scored_experts"] % cfg["n_group"]:
        raise ValueError("dsv32_decode builder: configuration %r asks for "
                         "what the step does not build" % cfg["name"])
    return dict(
        vocab_size=cfg["vocab_size"], n_layer=cfg["num_hidden_layers"],
        n_dense=cfg["first_k_dense_replace"],
        n_head=cfg["num_attention_heads"], d_model=cfg["hidden_size"],
        q_rank=cfg["q_lora_rank"], kv_rank=cfg["kv_lora_rank"],
        d_nope=cfg["qk_nope_head_dim"], d_rope=cfg["qk_rope_head_dim"],
        d_v=cfg["v_head_dim"], d_ff=cfg["intermediate_size"],
        d_expert=cfg["moe_intermediate_size"],
        n_experts=cfg["scored_experts"],
        held=(cfg["first_expert"], cfg["n_routed_experts"]),
        top_k=cfg["num_experts_per_tok"],
        norm_topk=cfg["norm_topk_prob"],
        routed_scale=cfg["routed_scaling_factor"], eps=cfg["rms_norm_eps"],
        rope_theta=float(cfg["rope_theta"]), sandwich_norm=False,
        indexer=(cfg["index_n_heads"], cfg["index_head_dim"],
                 cfg["index_topk"]),
        n_group=cfg["n_group"], topk_group=cfg["topk_group"],
        router_bias=True,
        yarn={"factor": scaling["factor"],
              "original_positions":
                  scaling["original_max_position_embeddings"],
              "beta_fast": scaling["beta_fast"],
              "beta_slow": scaling["beta_slow"],
              "mscale": scaling["mscale_all_dim"]})


def build(cfg, batch, **changed):
    """{"main", "logits", "state_pairs", "param_names", "cache_shapes",
    "probes"} of the cached step at `batch` rows and the configuration's
    `serve_positions`; `changed` overrides builder arguments (a control
    of `correct` serves another `indexer`).

    "cache_shapes" is {feed: shape} of the two caches a layer.  "probes"
    is, per layer, (layer, {what: a state pair}) of what the step only
    writes and a decoder carries out of a call's last step: "attn_in"
    [batch, 1, hidden] the attention sub-layer's normed input,
    "selected" [batch, index_topk] the slots its attention read and
    "attn_out" [batch, 1, hidden] what the sub-layer gave for them; and
    for an expert layer pangu's three, "in", "idx", "out"."""
    import jax
    from paddle_tpu.models.latent_moe_program import (
        build_latent_moe_cached_step_program, latent_moe_param_names)

    positions = cfg["serve_positions"]
    main, _, logits, pairs, parts = build_latent_moe_cached_step_program(
        batch, positions, **dict(sizes(cfg), **changed))
    layers, dense = cfg["num_hidden_layers"], cfg["first_k_dense_replace"]
    probes = []
    for i in range(layers):
        found = {"attn_in": parts["attn_in"][i],
                 "selected": parts["selected"][i],
                 "attn_out": parts["attn_out"][i]}
        if i >= dense:
            found.update({what: parts[part][i - dense] for what, part in (
                ("in", "moe_in"), ("idx", "top_idx"), ("out", "moe_out"))})
        probes.append((i, {what: ("probe_%d.%s" % (i, what), var.name)
                           for what, var in found.items()}))
    names = latent_moe_param_names(layers, dense, sandwich_norm=False,
                                   indexer=True, router_bias=True)
    built = {p.name for p in main.global_block().all_parameters()}
    if set(jax.tree_util.tree_leaves(names)) != built:
        raise ValueError("the program's parameters %s are not those "
                         "configuration %r names"
                         % (sorted(built), cfg["name"]))
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
    """{name: (shape, kind)} of one block's parameters; pangu's kinds and
    "bias" and "index_query" (the spec's `qi_gain` multiplies it)."""
    d, heads = cfg["hidden_size"], cfg["num_attention_heads"]
    q, kv = cfg["q_lora_rank"], cfg["kv_lora_rank"]
    nope, rope, v = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                     cfg["v_head_dim"])
    ih, idim = cfg["index_n_heads"], cfg["index_head_dim"]
    out = {
        "input_norm": ((d,), "norm"), "w_dq": ((d, q), "matrix"),
        "q_norm": ((q,), "norm"),
        "w_uq_nope": ((q, heads * nope), "query"),
        "w_uq_rope": ((q, heads * rope), "query"),
        "w_dkv": ((d, kv + rope), "matrix"), "kv_norm": ((kv,), "norm"),
        "w_uk": ((kv, heads * nope), "matrix"),
        "w_uv": ((kv, heads * v), "matrix"),
        "wo": ((heads * v, d), "matrix"),
        "pre_mlp_norm": ((d,), "norm"),
        "w_iq": ((q, ih * idim), "index_query"),
        "w_ik": ((d, idim), "matrix"),
        "ik_norm": ((idim,), "norm"), "ik_norm_b": ((idim,), "bias"),
        "w_iw": ((d, ih), "matrix"),
    }
    if layer < cfg["first_k_dense_replace"]:
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
    """pangu_decode's `_draw` for its kinds; a bias is N(0, bias_std) in
    float32 (the router adds it to float32 scores); an index query
    matrix takes `qi_gain`."""
    if kind == "bias":
        return _pangu._draw(dict(spec, std=spec["bias_std"],
                                 dtype="float32"), key, name, shape,
                            "matrix")
    if kind == "index_query":
        return _pangu._draw(dict(spec, q_gain=spec.get("qi_gain", 1.0)),
                            key, name, shape, "query")
    return _pangu._draw(spec, key, name, shape, kind)


def block(cfg, spec, key, layer):
    """The parameters of block `layer` from the `root` key.  Pure jax."""
    return {name: _draw(spec, key, "block_%d.%s" % (layer, name), shape,
                        kind)
            for name, (shape, kind) in _shapes(cfg, layer).items()}


def ends(cfg, spec, key):
    """{"embed", "norm_f", "head"} from the `root` key."""
    return _pangu.ends(cfg, spec, key)


def weights(cfg, spec, key):
    """Every parameter from a seeded key in the type it is served in, as
    the tree benchmark/reference/deepseek_v32.py documents.  Pure jax:
    call it under one `jax.jit`."""
    key = root(key)
    tree = ends(cfg, spec, key)
    tree["blocks"] = [block(cfg, spec, key, i)
                      for i in range(cfg["num_hidden_layers"])]
    return tree


def documents(cfg, workload, seed):
    """The seeded documents whose sessions the rows continue,
    `[documents, session_len]` int32 on the host: uniform ids over the
    held rows of the vocabulary."""
    import numpy as np

    rng = np.random.default_rng([seed, 0xD0C5])
    return rng.integers(0, cfg["vocab_size"],
                        (workload["documents"], workload["session_len"]),
                        dtype=np.int32)


def prompts(cfg, workload, seed):
    """The pool of question batches, `[pool, batch, prompt_len]` int32:
    row r of a batch asks of document r // questions_a_document."""
    return _pangu.prompts(cfg, workload, seed)
