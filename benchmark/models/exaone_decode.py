"""One chip's share of K-EXAONE-236B-A23B as a cached decode step
Program, from a configuration file, with what the long-session cell
makes from the seed beside it.

The step is the program's own
`paddle_tpu.models.window_moe_program.build_window_moe_cached_step_program`
(one token in, the next token's logits over the held vocabulary out; a
ring of `sliding_window` slots a window layer and a cache of the whole
extent a full layer, both through the `cached_attention` op over grouped
key/value heads; the held range of the routed experts through
`moe_experts`) at the configuration's widths; `fluid.ProgramDecoder`
scans it.

The weights are drawn as benchmark/models/pangu_decode.py draws them
(its `_draw`, `root`: a parameter's stream is its name's, so a block can
be made alone for the reference bit for bit as it is served), with two
kinds more: a bias, N(0, `bias_std`) in float32 (the router's selection
bias), and the scale of the queries' per-head RMSNorm, which `qk_gain`
multiplies (q and k are normed head by head, so a gain on W_q would be
normed away: a query's sharpness is its norm's scale, as it is in a
trained model with q/k norms).  `documents` and `prompts` (the
questions) are pure functions of the seed.
"""

import zlib

from benchmark import harness

_pangu = harness.Lookup().module("models", "pangu_decode")
root = _pangu.root

WINDOW, FULL = "sliding_attention", "full_attention"


def sizes(cfg):
    """The configuration's keys as the step builder's arguments."""
    layers = cfg["num_hidden_layers"]
    windows = [cfg["sliding_window"] if kind == WINDOW else 0
               for kind in cfg["layer_types"]]
    if cfg["num_shared_experts"] != 1 or cfg["tie_word_embeddings"] \
            or cfg["num_nextn_predict_layers"] \
            or cfg["scoring_func"] != "sigmoid" \
            or cfg["hidden_act"] != "silu" \
            or cfg["n_group"] != 1 or cfg["topk_group"] != 1 \
            or cfg["rope_parameters"]["rope_type"] != "default" \
            or len(cfg["layer_types"]) != layers \
            or len(cfg["mlp_layer_types"]) != layers \
            or list(cfg["sliding_windows"]) != windows \
            or cfg["mlp_layer_types"][:cfg["first_k_dense_replace"]] \
            != ["dense"] * cfg["first_k_dense_replace"]:
        raise ValueError("exaone_decode builder: configuration %r asks for "
                         "what the step does not build" % cfg["name"])
    return dict(
        vocab_size=cfg["vocab_size"], layer_types=tuple(cfg["layer_types"]),
        mlp_layer_types=tuple(cfg["mlp_layer_types"]),
        window=cfg["sliding_window"], n_head=cfg["num_attention_heads"],
        n_kv_head=cfg["num_key_value_heads"], d_head=cfg["head_dim"],
        d_model=cfg["hidden_size"], d_ff=cfg["intermediate_size"],
        d_expert=cfg["moe_intermediate_size"],
        n_experts=cfg["scored_experts"],
        held=(cfg["first_expert"], cfg["num_experts"]),
        top_k=cfg["num_experts_per_tok"], norm_topk=cfg["norm_topk_prob"],
        routed_scale=cfg["routed_scaling_factor"], eps=cfg["rms_norm_eps"],
        rope_theta=float(cfg["rope_parameters"]["rope_theta"]))


def cache_shapes(cfg, batch, window=None):
    """{feed: shape} of the two caches a layer: a ring on a window
    layer, the whole of `serve_positions` on a full one."""
    window = window or cfg["sliding_window"]
    return {"%s_cache_%d" % (which, i): (
        batch, cfg["num_key_value_heads"],
        window if kind == WINDOW else cfg["serve_positions"],
        cfg["head_dim"])
        for i, kind in enumerate(cfg["layer_types"]) for which in "kv"}


def build(cfg, batch, **changed):
    """{"main", "logits", "state_pairs", "param_names", "cache_shapes",
    "probes"} of the cached step at `batch` rows and the configuration's
    `serve_positions`; `changed` overrides builder arguments (a control
    of `correct` serves another `window`).

    "probes" is, per layer, (layer, {what: a state pair}) of what the
    step only writes and a decoder carries out of a call's last step:
    "attn_in" [batch, 1, hidden] the attention sub-layer's normed input
    and "attn_out" [batch, 1, hidden] what the sub-layer gave for it;
    and for an expert layer pangu's three, "in", "idx", "out"."""
    import jax
    from paddle_tpu.models.window_moe_program import (
        build_window_moe_cached_step_program, window_moe_param_names)

    args = dict(sizes(cfg), **changed)
    main, _, logits, pairs, parts = build_window_moe_cached_step_program(
        batch, cfg["serve_positions"], **args)
    probes, sparse = [], 0
    for i, kind in enumerate(cfg["mlp_layer_types"]):
        found = {"attn_in": parts["attn_in"][i],
                 "attn_out": parts["attn_out"][i]}
        if kind == "sparse":
            found.update({what: parts[part][sparse] for what, part in (
                ("in", "moe_in"), ("idx", "top_idx"), ("out", "moe_out"))})
            sparse += 1
        probes.append((i, {what: ("probe_%d.%s" % (i, what), var.name)
                           for what, var in found.items()}))
    names = window_moe_param_names(cfg["mlp_layer_types"])
    built = {p.name for p in main.global_block().all_parameters()}
    if set(jax.tree_util.tree_leaves(names)) != built:
        raise ValueError("the program's parameters %s are not those "
                         "configuration %r names"
                         % (sorted(built), cfg["name"]))
    return {"main": main, "logits": logits, "state_pairs": pairs,
            "param_names": names, "probes": probes,
            "cache_shapes": cache_shapes(cfg, batch, args["window"])}


def _shapes(cfg, layer):
    """{name: (shape, kind)} of one block's parameters; pangu's kinds and
    "bias" and "query_norm" (the spec's `qk_gain` multiplies it)."""
    d, dim = cfg["hidden_size"], cfg["head_dim"]
    heads, kv_heads = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    out = {
        "input_norm": ((d,), "norm"),
        "wq": ((d, heads * dim), "matrix"),
        "wk": ((d, kv_heads * dim), "matrix"),
        "wv": ((d, kv_heads * dim), "matrix"),
        "q_norm": ((dim,), "query_norm"), "k_norm": ((dim,), "norm"),
        "wo": ((heads * dim, d), "matrix"),
        "pre_mlp_norm": ((d,), "norm"),
    }
    if cfg["mlp_layer_types"][layer] == "dense":
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
    return out


def _draw(spec, key, name, shape, kind):
    """pangu_decode's `_draw` for its kinds; a bias is N(0, bias_std) in
    float32 (the router adds it to float32 scores); the queries' norm
    scale is `qk_gain` (1 + N(0, std)): pangu's integer sum times one
    float32 constant, the gain folded into it, so that no compilation
    rounds it another way."""
    import jax
    import jax.numpy as jnp

    if kind == "bias":
        return _pangu._draw(dict(spec, std=spec["bias_std"],
                                 dtype="float32"), key, name, shape,
                            "matrix")
    if kind != "query_norm":
        return _pangu._draw(spec, key, name, shape, kind)
    std, four = spec["std"], _pangu._FOUR_BYTES_STD
    word = jax.random.bits(
        jax.random.fold_in(key, zlib.crc32(name.encode()) & 0x7FFFFFFF),
        shape, jnp.uint32)
    drawn = ((word & 255) + ((word >> 8) & 255) + ((word >> 16) & 255)
             + (word >> 24)).astype(jnp.int32) - 510 + int(round(four / std))
    unit = jnp.float32(spec.get("qk_gain", 1.0) * std / four)
    return (drawn.astype(jnp.float32) * unit).astype(jnp.dtype(spec["dtype"]))


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
    the tree benchmark/reference/exaone_moe.py documents.  Pure jax:
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
