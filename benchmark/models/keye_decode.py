"""One chip's share of the language model of Keye-VL-2.0-30B-A3B as a
cached decode step Program, from a configuration file, with what the
sparse key/value cell makes from the seed beside it.

The step is the program's own
`paddle_tpu.models.sparse_kv_moe_program.build_sparse_kv_moe_cached_step_program`
(one token in, the next token's logits over the held vocabulary out;
keys, values and the chooser's keys of the whole extent a layer;
`mla_index_select` picks `sa_config.topk` slots and `cached_attention`
reads those alone; q and k turned by three-part positions; the held
range of the softmax-routed experts through `moe_experts`, no shared
expert) at the configuration's widths; `fluid.ProgramDecoder` scans it.

The weights are drawn as benchmark/models/exaone_decode.py draws them
(its `_draw`: pangu's kinds, a float32 bias, and the queries' per-head
norm scale times `qk_gain`), the chooser's queries' matrix times
`qi_gain` (dsv32_decode's kind).  `documents`, `prompts` (the questions)
and `images` (where a document's image spans lie, the vectors a tower
would have handed over for them, the three-part positions that follow)
are pure functions of the seed.
"""

from benchmark import harness

_lookup = harness.Lookup()
_pangu = _lookup.module("models", "pangu_decode")
_exaone = _lookup.module("models", "exaone_decode")
root = _pangu.root


def sizes(cfg):
    """The configuration's keys as the step builder's arguments."""
    sa, scaling = cfg["sa_config"], cfg["rope_scaling"]
    if cfg["attention_bias"] or cfg["tie_word_embeddings"] \
            or cfg["use_sliding_window"] or cfg["mlp_only_layers"] \
            or cfg["decoder_sparse_step"] != 1 \
            or cfg["hidden_act"] != "silu" \
            or scaling["rope_type"] != "default" \
            or sa["indexer_num_kv_heads"] != 1 \
            or 2 * sum(scaling["mrope_section"]) != cfg["head_dim"]:
        raise ValueError("keye_decode builder: configuration %r asks for "
                         "what the step does not build" % cfg["name"])
    return dict(
        vocab_size=cfg["vocab_size"], n_layer=cfg["num_hidden_layers"],
        n_head=cfg["num_attention_heads"],
        n_kv_head=cfg["num_key_value_heads"], d_head=cfg["head_dim"],
        d_model=cfg["hidden_size"], d_expert=cfg["moe_intermediate_size"],
        n_experts=cfg["scored_experts"],
        held=(cfg["first_expert"], cfg["num_experts"]),
        top_k=cfg["num_experts_per_tok"], norm_topk=cfg["norm_topk_prob"],
        eps=cfg["rms_norm_eps"], rope_theta=float(cfg["rope_theta"]),
        sections=tuple(scaling["mrope_section"]),
        indexer=(sa["indexer_num_heads"], sa["indexer_head_dim"],
                 sa["topk"]))


def cache_shapes(cfg, batch):
    """{feed: shape} of the three caches a layer."""
    positions, sa = cfg["serve_positions"], cfg["sa_config"]
    shapes = {}
    for i in range(cfg["num_hidden_layers"]):
        for which in "kv":
            shapes["%s_cache_%d" % (which, i)] = (
                batch, cfg["num_key_value_heads"], positions,
                cfg["head_dim"])
        shapes["index_cache_%d" % i] = (batch, positions,
                                        sa["indexer_head_dim"])
    return shapes


def build(cfg, batch, **changed):
    """{"main", "logits", "state_pairs", "param_names", "cache_shapes",
    "probes"} of the cached step at `batch` rows and the configuration's
    `serve_positions`; `changed` overrides builder arguments (a control
    of `correct` serves another `indexer`).

    "probes" is, per layer, (layer, {what: a state pair}) of what the
    step only writes and a decoder carries out of a call's last step:
    "attn_in" [batch, 1, hidden] the attention sub-layer's normed input,
    "selected" [batch, topk] the slots its attention read, "attn_out"
    [batch, 1, hidden] what the sub-layer gave for them, and pangu's
    three of the expert layer, "in", "idx", "out"."""
    import jax
    from paddle_tpu.models.sparse_kv_moe_program import (
        build_sparse_kv_moe_cached_step_program, sparse_kv_moe_param_names)

    main, _, logits, pairs, parts = build_sparse_kv_moe_cached_step_program(
        batch, cfg["serve_positions"], **dict(sizes(cfg), **changed))
    probes = [(i, {what: ("probe_%d.%s" % (i, what), parts[part][i].name)
                   for what, part in (
                       ("attn_in", "attn_in"), ("selected", "selected"),
                       ("attn_out", "attn_out"), ("in", "moe_in"),
                       ("idx", "top_idx"), ("out", "moe_out"))})
              for i in range(cfg["num_hidden_layers"])]
    names = sparse_kv_moe_param_names(cfg["num_hidden_layers"])
    built = {p.name for p in main.global_block().all_parameters()}
    if set(jax.tree_util.tree_leaves(names)) != built:
        raise ValueError("the program's parameters %s are not those "
                         "configuration %r names"
                         % (sorted(built), cfg["name"]))
    return {"main": main, "logits": logits, "state_pairs": pairs,
            "param_names": names, "probes": probes,
            "cache_shapes": cache_shapes(cfg, batch)}


def _shapes(cfg):
    """{name: (shape, kind)} of a block's parameters; exaone's kinds and
    "index_query" (the spec's `qi_gain` multiplies it)."""
    d, dim = cfg["hidden_size"], cfg["head_dim"]
    heads, kv_heads = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    sa = cfg["sa_config"]
    ih, idim = sa["indexer_num_heads"], sa["indexer_head_dim"]
    f, held = cfg["moe_intermediate_size"], cfg["num_experts"]
    return {
        "input_norm": ((d,), "norm"),
        "wq": ((d, heads * dim), "matrix"),
        "wk": ((d, kv_heads * dim), "matrix"),
        "wv": ((d, kv_heads * dim), "matrix"),
        "q_norm": ((dim,), "query_norm"), "k_norm": ((dim,), "norm"),
        "wo": ((heads * dim, d), "matrix"),
        "w_iq": ((d, ih * idim), "index_query"),
        "w_ik": ((d, idim), "matrix"),
        "ik_norm": ((idim,), "norm"), "ik_norm_b": ((idim,), "bias"),
        "w_iw": ((d, ih), "matrix"),
        "pre_mlp_norm": ((d,), "norm"),
        "router": ((d, cfg["scored_experts"]), "matrix"),
        "w_gate": ((held, d, f), "routed"), "w_up": ((held, d, f), "routed"),
        "w_down": ((held, f, d), "routed")}


def _draw(spec, key, name, shape, kind):
    if kind == "index_query":
        return _pangu._draw(dict(spec, q_gain=spec.get("qi_gain", 1.0)),
                            key, name, shape, "query")
    return _exaone._draw(spec, key, name, shape, kind)


def block(cfg, spec, key, layer):
    """The parameters of block `layer` from the `root` key.  Pure jax."""
    return {name: _draw(spec, key, "block_%d.%s" % (layer, name), shape,
                        kind)
            for name, (shape, kind) in _shapes(cfg).items()}


def ends(cfg, spec, key):
    """{"embed", "norm_f", "head"} from the `root` key."""
    return _pangu.ends(cfg, spec, key)


def weights(cfg, spec, key):
    """Every parameter from a seeded key in the type it is served in, as
    the tree benchmark/reference/keye_vl2.py documents.  Pure jax: call
    it under one `jax.jit`."""
    key = root(key)
    tree = ends(cfg, spec, key)
    tree["blocks"] = [block(cfg, spec, key, i)
                      for i in range(cfg["num_hidden_layers"])]
    return tree


def documents(cfg, workload, seed):
    """The seeded documents whose sessions the rows continue,
    `[documents, session_len]` int32 on the host: uniform ids over the
    held rows of the vocabulary (at an image's slots the id is unread:
    the tower's vector stands there)."""
    import numpy as np

    rng = np.random.default_rng([seed, 0xD0C5])
    return rng.integers(0, cfg["vocab_size"],
                        (workload["documents"], workload["session_len"]),
                        dtype=np.int32)


def images(cfg, workload, seed):
    """What a vision tower and the layout of a document would hand the
    language model, from the seed: {"spans": [documents] x [(slot, h,
    w)], `image_spans` images of `image_grid` tokens a document, one at
    a seeded slot inside each of as many equal stretches of the session;
    "slots" [documents, n] the slots they cover; "vectors" [documents,
    n, hidden] float32 N(0, weights.embed_std), the tower's outputs;
    "positions" [3, documents, session_len] and "rope_delta" (the same
    for every document: every image advances the position by max(h, w)
    over its h * w slots)}."""
    import numpy as np

    reference = _lookup.module("reference", workload["reference"])
    count, (h, w) = workload["image_spans"], workload["image_grid"]
    docs, length = workload["documents"], workload["session_len"]
    stretch = length // count
    if count and stretch < h * w:
        raise ValueError("%d images of %d x %d tokens do not fit %d slots"
                         % (count, h, w, length))
    rng = np.random.default_rng([seed, 0x1A6E])
    spans, slots, positions = [], [], []
    for _ in range(docs):
        starts = np.arange(count) * stretch \
            + rng.integers(0, stretch - h * w + 1, count)
        spans.append([(int(s), h, w) for s in starts])
        slots.append((starts[:, None] + np.arange(h * w)).reshape(-1))
        at, after = reference.layout(length, spans[-1])
        positions.append(at)
    vectors = rng.standard_normal(
        (docs, count * h * w, cfg["hidden_size"]), dtype=np.float32) \
        * np.float32(workload["weights"].get("embed_std", 1.0))
    return {"spans": spans, "slots": np.stack(slots),
            "vectors": vectors, "positions": np.stack(positions, axis=1),
            "rope_delta": after - length}


def prompts(cfg, workload, seed):
    """The pool of question batches, `[pool, batch, prompt_len]` int32:
    row r of a batch asks of document r // questions_a_document."""
    return _pangu.prompts(cfg, workload, seed)
