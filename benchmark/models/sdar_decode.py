"""One pipeline stage of SDAR-30B-A3B-Chat as a cached step Program that
takes a block of positions under a block-causal mask, from a
configuration file, with what the block-diffusion cell makes from the
seed beside it.

The step is the program's own
`paddle_tpu.models.diffusion_moe_program.build_diffusion_moe_cached_step_program`
(T tokens of every row in, the logits of every one of them out; keys and
values of the whole extent a layer through `cached_attention` with
`diffusion_block`; q and k normed head by head and turned rotate-half;
all the softmax-routed experts through `moe_experts`, no shared one) at
the configuration's widths; `fluid.ProgramDecoder.diffuse` runs it.

The weights are drawn as benchmark/models/keye_decode.py draws them for
the same layer without its chooser (exaone_decode's `_draw`: pangu's
kinds and the queries' per-head norm scale times `qk_gain`), the head
times `head_gain`.  `prompts` is a pure function of the seed.
"""

from benchmark import harness

_lookup = harness.Lookup()
_pangu = _lookup.module("models", "pangu_decode")
_exaone = _lookup.module("models", "exaone_decode")
root = _pangu.root
prompts = _pangu.prompts


def sizes(cfg):
    """The configuration's keys as the step builder's arguments."""
    if cfg["attention_bias"] or cfg["tie_word_embeddings"] \
            or cfg["use_sliding_window"] or cfg["mlp_only_layers"] \
            or cfg["decoder_sparse_step"] != 1 \
            or cfg["hidden_act"] != "silu" or cfg["rope_scaling"]:
        raise ValueError("sdar_decode builder: configuration %r asks for "
                         "what the step does not build" % cfg["name"])
    return dict(
        vocab_size=cfg["vocab_size"],
        block_length=cfg["generation"]["block_length"],
        n_layer=cfg["num_hidden_layers"],
        n_head=cfg["num_attention_heads"],
        n_kv_head=cfg["num_key_value_heads"], d_head=cfg["head_dim"],
        d_model=cfg["hidden_size"], d_expert=cfg["moe_intermediate_size"],
        n_experts=cfg["num_experts"], top_k=cfg["num_experts_per_tok"],
        norm_topk=cfg["norm_topk_prob"], eps=cfg["rms_norm_eps"],
        rope_theta=float(cfg["rope_theta"]))


def cache_shapes(cfg, batch):
    """{feed: shape} of the two caches a layer."""
    return {"%s_cache_%d" % (which, i): (
        batch, cfg["num_key_value_heads"], cfg["serve_positions"],
        cfg["head_dim"])
        for i in range(cfg["num_hidden_layers"]) for which in "kv"}


def build(cfg, batch, probe_rows=0, **changed):
    """{"main", "logits", "state_pairs", "param_names", "cache_shapes",
    "probes"} of the cached step at `batch` rows and the configuration's
    `serve_positions`; `changed` overrides builder arguments.

    "probes" is, with `probe_rows` > 0, {"keys", "values": a state pair}
    of what the step only writes and a decoder carries out of a call's
    last pass: the first `probe_rows` rows of the **first** layer's
    caches as handed on, [probe_rows, kv heads, serve_positions, dim]."""
    import jax
    from paddle_tpu.models.diffusion_moe_program import (
        build_diffusion_moe_cached_step_program, diffusion_moe_param_names)

    main, _, logits, pairs, parts = build_diffusion_moe_cached_step_program(
        batch, cfg["serve_positions"],
        **dict(sizes(cfg), probe_rows=probe_rows, **changed))
    probes = {what: ("probe.%s" % what, parts[what][0].name)
              for what in parts}
    names = diffusion_moe_param_names(cfg["num_hidden_layers"])
    built = {p.name for p in main.global_block().all_parameters()}
    if set(jax.tree_util.tree_leaves(names)) != built:
        raise ValueError("the program's parameters %s are not those "
                         "configuration %r names"
                         % (sorted(built), cfg["name"]))
    return {"main": main, "logits": logits, "state_pairs": pairs,
            "param_names": names, "probes": probes,
            "cache_shapes": cache_shapes(cfg, batch)}


def _shapes(cfg):
    """{name: (shape, kind)} of a block's parameters; exaone's kinds."""
    d, dim = cfg["hidden_size"], cfg["head_dim"]
    heads, kv_heads = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    f, experts = cfg["moe_intermediate_size"], cfg["num_experts"]
    return {
        "input_norm": ((d,), "norm"),
        "wq": ((d, heads * dim), "matrix"),
        "wk": ((d, kv_heads * dim), "matrix"),
        "wv": ((d, kv_heads * dim), "matrix"),
        "q_norm": ((dim,), "query_norm"), "k_norm": ((dim,), "norm"),
        "wo": ((heads * dim, d), "matrix"),
        "pre_mlp_norm": ((d,), "norm"),
        "router": ((d, experts), "matrix"),
        "w_gate": ((experts, d, f), "routed"),
        "w_up": ((experts, d, f), "routed"),
        "w_down": ((experts, f, d), "routed")}


def block(cfg, spec, key, layer):
    """The parameters of block `layer` from the `root` key.  Pure jax."""
    return {name: _exaone._draw(spec, key, "block_%d.%s" % (layer, name),
                                shape, kind)
            for name, (shape, kind) in _shapes(cfg).items()}


def ends(cfg, spec, key):
    """{"embed", "norm_f", "head"} from the `root` key: pangu's, the
    head's N(0, std) times `head_gain` (how peaked a position's
    prediction is: the confidences the passes fix by)."""
    d, vocab = cfg["hidden_size"], cfg["vocab_size"]
    made = _pangu.ends(cfg, spec, key)
    made["head"] = _pangu._draw(
        dict(spec, std=spec["std"] * spec.get("head_gain", 1.0)), key,
        "head", (d, vocab), "matrix")
    return made


def weights(cfg, spec, key):
    """Every parameter from a seeded key in the type it is served in, as
    the tree benchmark/reference/sdar_moe.py documents.  Pure jax: call
    it under one `jax.jit`."""
    key = root(key)
    tree = ends(cfg, spec, key)
    tree["blocks"] = [block(cfg, spec, key, i)
                      for i in range(cfg["num_hidden_layers"])]
    return tree
