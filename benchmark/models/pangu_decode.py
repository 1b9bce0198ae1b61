"""One chip's share of openPangu-Ultra-MoE-718B as a cached decode step
Program, from a configuration file, with what a generation cell makes
from the seed beside it.

The step is the program's own
`paddle_tpu.models.latent_moe_program.build_latent_moe_cached_step_program`
(one token in, the next token's logits over the held vocabulary out, one
cache of latents a layer through the `mla_cached_attention` op, the held
range of the routed experts through `moe_experts`) at the
configuration's widths; `fluid.ProgramDecoder` scans it.

`weights`, `block`, `ends` and `prompts` are pure functions of the seed and
import nothing of the program: the driver hands their arrays to the
program, and the plain reference (benchmark/reference/pangu_moe.py)
makes its own, part by part, from the same seed.
"""

import zlib


def sizes(cfg):
    """The configuration's keys as the step builder's arguments."""
    if cfg["num_key_value_heads"] != cfg["num_attention_heads"] \
            or cfg["n_shared_experts"] != 1 or not cfg["sandwich_norm"] \
            or cfg["attention_bias"] or cfg["tie_word_embeddings"] \
            or cfg["num_nextn_predict_layers"]:
        raise ValueError("pangu_decode builder: configuration %r asks for "
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
        rope_theta=float(cfg["rope_theta"]))


def build(cfg, batch):
    """{"main", "logits", "state_pairs", "param_names", "cache_names",
    "cache_shape", "probes"} of the cached step at `batch` rows and the
    configuration's `serve_positions`.

    "probes" is, per expert layer, (layer, {"in", "idx", "out": a state
    pair}): the routed layer's input [batch, 1, hidden], the router's
    chosen experts [batch, top_k] and the held experts' part [batch, 1,
    hidden] as state pairs whose feed the step does not read, so that a
    decoder carries what the step wrote and its caller can read, after a
    call, what the expert layers of the call's last step were handed and
    gave.  They are not among "state_pairs": a caller that wants them
    appends them."""
    import jax
    from paddle_tpu.models.latent_moe_program import (
        build_latent_moe_cached_step_program, latent_moe_param_names)

    positions = cfg["serve_positions"]
    main, _, logits, pairs, parts = build_latent_moe_cached_step_program(
        batch, positions, **sizes(cfg))
    dense = cfg["first_k_dense_replace"]
    probes = [(dense + k, {
        what: ("held_part_%d.%s" % (dense + k, what), parts[part][k].name)
        for what, part in (("in", "moe_in"), ("idx", "top_idx"),
                           ("out", "moe_out"))})
        for k in range(cfg["num_hidden_layers"] - dense)]
    names = latent_moe_param_names(cfg["num_hidden_layers"],
                                   cfg["first_k_dense_replace"])
    built = {p.name for p in main.global_block().all_parameters()}
    if set(jax.tree_util.tree_leaves(names)) != built:
        raise ValueError("the program's parameters %s are not those "
                         "configuration %r names"
                         % (sorted(built), cfg["name"]))
    return {"main": main, "logits": logits, "state_pairs": pairs,
            "param_names": names, "probes": probes,
            "cache_names": [feed for feed, _ in pairs if feed != "pos"],
            "cache_shape": (batch, positions,
                            cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"])}


def _shapes(cfg, layer):
    """{name: (shape, kind)} of one block's parameters; kind is "norm",
    "matrix", "query" (a matrix the spec's `q_gain` multiplies) or
    "routed" (a stack of held experts)."""
    d, heads = cfg["hidden_size"], cfg["num_attention_heads"]
    q, kv = cfg["q_lora_rank"], cfg["kv_lora_rank"]
    nope, rope, v = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                     cfg["v_head_dim"])
    out = {
        "input_norm": ((d,), "norm"), "w_dq": ((d, q), "matrix"),
        "q_norm": ((q,), "norm"),
        "w_uq_nope": ((q, heads * nope), "query"),
        "w_uq_rope": ((q, heads * rope), "query"),
        "w_dkv": ((d, kv + rope), "matrix"), "kv_norm": ((kv,), "norm"),
        "w_uk": ((kv, heads * nope), "matrix"),
        "w_uv": ((kv, heads * v), "matrix"),
        "wo": ((heads * v, d), "matrix"),
        "post_attn_norm": ((d,), "norm"), "pre_mlp_norm": ((d,), "norm"),
        "post_mlp_norm": ((d,), "norm"),
    }
    if layer < cfg["first_k_dense_replace"]:
        f = cfg["intermediate_size"]
        out.update(ffn_in=((d, 2 * f), "matrix"), ffn_out=((f, d), "matrix"))
    else:
        f, held = cfg["moe_intermediate_size"], cfg["n_routed_experts"]
        out.update(
            shared_in=((d, 2 * f), "matrix"), shared_out=((f, d), "matrix"),
            router=((d, cfg["scored_experts"]), "matrix"),
            w_gate=((held, d, f), "routed"), w_up=((held, d, f), "routed"),
            w_down=((held, f, d), "routed"))
    return out


# the standard deviation of the sum of four bytes
_FOUR_BYTES_STD = (4 * (256 ** 2 - 1) / 12.0) ** 0.5


def _draw(spec, key, name, shape, kind):
    """One parameter from the seeded key, in the type it is served in:
    matrices N(0, std), norm scales 1 + N(0, std) so that a scale left
    out is seen.  A parameter's stream is its name's, so any part of the
    tree can be made alone, and bit for bit as it is made with the rest:
    a draw is the sum of the four bytes of a random word less its mean
    (near-normal, within 3.45 deviations), an integer, times one float32
    constant, so that no compilation can round it another way (a
    polynomial `erfinv` comes out an ulp apart when it is fused
    otherwise, which is a bfloat16 weight apart now and then)."""
    import jax
    import jax.numpy as jnp

    dtype, std = jnp.dtype(spec["dtype"]), spec["std"]
    word = jax.random.bits(
        jax.random.fold_in(key, zlib.crc32(name.encode()) & 0x7FFFFFFF),
        shape, jnp.uint32)
    drawn = ((word & 255) + ((word >> 8) & 255) + ((word >> 16) & 255)
             + (word >> 24)).astype(jnp.int32) - 510
    if kind == "embed":
        std = spec.get("embed_std", std)
    elif kind == "query":
        std = std * spec.get("q_gain", 1.0)
    unit = jnp.float32(std / _FOUR_BYTES_STD)
    if kind == "norm":
        drawn = drawn + int(round(_FOUR_BYTES_STD / std))
    value = drawn.astype(jnp.float32) * unit
    if kind == "routed" and "routed_mantissa_bits" in spec:
        # the routed experts' weights at a narrower type's precision,
        # read up to the served one: what `correct`'s control of them
        # switches on.  (An explicit rounding: XLA drops a cast to a
        # narrower type that is followed by a cast back up.)
        value = jax.lax.reduce_precision(
            value, exponent_bits=8,
            mantissa_bits=spec["routed_mantissa_bits"])
    return value.astype(dtype)


def root(key):
    """The key every parameter's stream is folded from: `key` (a
    `jax.random.PRNGKey`) as a key of the "rbg" implementation, whose
    bits are the device's own generator's (a pure function of key and
    shape, like threefry's, and a dozen times faster for the share's 4.9
    billion values)."""
    import jax
    import jax.numpy as jnp

    data = jnp.tile(jnp.asarray(key, jnp.uint32).reshape(-1)[:2], 2)
    return jax.random.fold_in(
        jax.random.wrap_key_data(data, impl="rbg"), 0x9A96)


def block(cfg, spec, key, layer):
    """The parameters of block `layer` from the `root` key.  Pure jax."""
    return {name: _draw(spec, key, "block_%d.%s" % (layer, name), shape,
                        kind)
            for name, (shape, kind) in _shapes(cfg, layer).items()}


def ends(cfg, spec, key):
    """{"embed", "norm_f", "head"} from the `root` key."""
    d, vocab = cfg["hidden_size"], cfg["vocab_size"]
    return {"embed": _draw(spec, key, "embed", (vocab, d), "embed"),
            "norm_f": _draw(spec, key, "norm_f", (d,), "norm"),
            "head": _draw(spec, key, "head", (d, vocab), "matrix")}


def weights(cfg, spec, key):
    """Every parameter from a seeded key in the type it is served in
    (`spec`: the workload's `weights`), as the tree
    benchmark/reference/pangu_moe.py documents.  Pure jax: call it under
    one `jax.jit`.

    What the spec asks for beside N(0, std), each because `correct` has
    to see through it (the workload's `weights.why`): `embed_std` draws
    the token embedding wider, so that a token's identity is not lost
    under the first normed sub-layer output added to it; `q_gain`
    multiplies both query up-projections, so that a query's scores
    spread and it attends a few latents and not the mean of all of them
    (a mean over hundreds of slots averages a narrower cache's rounding
    away)."""
    key = root(key)
    tree = ends(cfg, spec, key)
    tree["blocks"] = [block(cfg, spec, key, i)
                      for i in range(cfg["num_hidden_layers"])]
    return tree


def prompts(cfg, workload, seed):
    """The pool of prompt batches, `[pool, batch, prompt_len]` int32 on
    the host: uniform ids over the held rows of the vocabulary.  Every
    seed gives the same sizes."""
    import numpy as np

    rng = np.random.default_rng([seed, 0x9E3779B9])
    return rng.integers(
        0, cfg["vocab_size"],
        (workload["pool"], workload["batch"], workload["prompt_len"]),
        dtype=np.int32)
