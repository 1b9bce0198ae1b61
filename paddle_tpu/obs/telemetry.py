"""Step-level run telemetry on top of obs.trace + obs.registry.

What the training/serving loops report here (and what every perf PR
reads back):

  * executor:  run counts, jit trace/compile detections (with instant
               trace events so a Perfetto timeline shows WHERE the
               stall was), host<->device transfer bytes from the
               feed/fetch paths — the costs that are otherwise
               *inferred* from step-time noise.
  * trainers:  per-step wall time, examples/sec, steps, last loss —
               one labeled metric family shared by the v2 SGD loop and
               the mesh-parallel trainer (`trainer` label).
  * scalars:   loss-scale / grad-norm style gauges via `set_gauge`.
  * jit:       seconds JAX spent tracing, lowering and compiling each
               jitted function (`jit_phase_seconds_total`), from
               `jax.monitoring`: the set-up time a warm compile cache
               cannot save is the trace and lower phases; and what
               JAX's persistent cache served or had to compile
               (`compile_cache_{hits,misses}_total`).

Everything funnels into the default registry (`obs.registry`), so one
Prometheus scrape / `obs_dump` call sees executor, trainer and serving
metrics side by side.  All helpers are cheap enough to call
unconditionally: a counter inc is one dict lookup + locked add.
"""

import time

import jax.monitoring

from . import registry as registry_mod
from . import trace as trace_mod

__all__ = ["on_executor_run", "on_jit_trace",
           "on_flash_attention_lowering",
           "on_flash_attention_bwd_lowering",
           "on_flash_attention_pairs",
           "on_flash_attention_grad_lowering", "on_moe_lowering",
           "on_moe_gmm_lowering", "on_moe_share_lowering",
           "on_moe_grouped_router_lowering",
           "on_mla_cached_attention_lowering",
           "on_mla_decode_lowering",
           "on_mla_index_select_lowering", "on_cached_attention_lowering",
           "on_window_attention_lowering", "on_flash_window_lowering",
           "on_sparse_attention_lowering", "on_sectioned_rope_lowering",
           "on_moe_share_bwd_lowering", "on_moe_share_compact_lowering",
           "on_prefill_lowering", "on_ssd_lowering",
           "on_causal_conv1d_lowering", "on_causal_conv1d_tail_lowering",
           "on_gated_delta_rule_lowering", "on_shared_parameter_uses",
           "on_index_sets_reused", "on_selective_scan_lowering",
           "on_cached_attention_readonly_lowering",
           "on_decoder_positions", "on_shared_cache_readers",
           "on_transfer",
           "on_decoder_call",
           "jit_trace_count", "transfer_bytes", "step", "set_gauge",
           "snapshot", "snapshot_delta", "snapshot_and_delta"]

# histogram bounds for step wall time: sub-ms tiny CPU steps up to
# multi-second compile-included first steps
STEP_SECONDS_BUCKETS = (0.001, 0.0025, 0.005, 0.01, 0.025, 0.05, 0.1,
                        0.25, 0.5, 1.0, 2.5, 5.0, 10.0, 30.0, 60.0)


def _reg():
    return registry_mod.get_registry()


# ---------------------------------------------------------------------------
# executor-side hooks
# ---------------------------------------------------------------------------

def on_executor_run():
    """One Executor.run() dispatch (any program)."""
    _reg().counter("executor_runs_total",
                   "Executor.run() invocations").inc()


def on_jit_trace(label):
    """A jitted segment specialized (traced + compiled) — the event
    that turns into a multi-second stall on TPU.  Counted per segment
    label and marked on the trace timeline."""
    _reg().counter("executor_jit_traces_total",
                   "XLA trace/compile events detected across jitted "
                   "segments").inc()
    trace_mod.instant("jit_trace", cat="compile", label=label)


def jit_trace_count():
    return _reg().counter("executor_jit_traces_total",
                          "XLA trace/compile events detected across "
                          "jitted segments").value


def on_flash_attention_lowering(block_q, block_k, kv_resident,
                                heads_per_step):
    """The flash-attention forward kernel was traced into a program,
    with the tiling it chose from the shapes (or was told) and the
    heads one grid step holds: 1, or the 2 (4, ...) narrow heads that
    share a 128-lane block of [batch, seq, heads * dim] operands, or
    "split" where operands so laid out were split into
    [batch, heads, seq, dim] around the kernel, no lane block holding
    whole heads.  One count per kernel instance a lowered program
    holds."""
    _reg().counter("flash_attention_lowerings_total",
                   "flash-attention forward kernels lowered, by tiling "
                   "and heads a grid step",
                   labelnames=("block_q", "block_k", "kv_resident",
                               "heads_per_step")) \
          .labels(block_q=block_q, block_k=block_k,
                  kv_resident=str(bool(kv_resident)).lower(),
                  heads_per_step=heads_per_step).inc()


def on_flash_attention_bwd_lowering(kernel, block_q, block_k,
                                    heads_per_step):
    """One of the flash-attention backward kernels ("dq_dkv", the one
    that makes all three gradients with a head's queries resident;
    "ring", the one that makes them walking the keys under a window, a
    ring of dq^T resident; or "dkv" and "dq", the two that walk) was
    traced into a program, with the tiling chosen for it and
    the heads one grid step holds (as `on_flash_attention_lowering`
    says): one count per kernel instance a lowered program holds."""
    _reg().counter("flash_attention_bwd_lowerings_total",
                   "flash-attention backward kernels lowered, by kernel, "
                   "tiling and heads a grid step",
                   labelnames=("kernel", "block_q", "block_k",
                               "heads_per_step")) \
          .labels(kernel=kernel, block_q=block_q, block_k=block_k,
                  heads_per_step=heads_per_step).inc()


def on_flash_attention_pairs(kernel_pass, folded, attended):
    """A flash-attention kernel of the forward ("fwd") or backward
    ("bwd") pass was traced into a program: `folded`, the (query, key)
    score pairs it computes over all its batch and heads, and
    `attended`, those among them a query attends, both from the static
    shapes (`kernels/flash_attention.py score_pairs`).  folded /
    attended says what the causal mask throws away: a chunk the diagonal
    crosses costs (n + 1) / 2n of its pairs as a staircase of n pieces
    of 256 keys (folded / attended 1.25 on gpt2m-train's 1024 tokens,
    1.06 on ouro-train-4k's 4096, in both passes), and all of them
    where the chunk is folded whole: where the blocks or `q_offset` are
    no multiple of 128, so that where the diagonal enters a chunk is
    not known when the kernel is traced (a decode step, a ragged
    sequence that is one block, a sequence shard at an odd offset), and
    in the two backward kernels that walk (1.50 at 512 x 512 blocks on
    1024 tokens, 1.25 and 1.125 at 1024 x 512 and 512 x 256 on 4096).
    One increment per kernel instance a lowered program holds; the two
    backward kernels that walk both count."""
    counter = _reg().counter(
        "flash_attention_pairs_total",
        "score pairs the flash-attention kernels lowered fold, and "
        "those a query attends", labelnames=("pass", "kind"))
    for kind, n in (("folded", folded), ("attended", attended)):
        counter.labels(**{"pass": kernel_pass, "kind": kind}).inc(n)


def on_flash_attention_grad_lowering(residuals):
    """A `flash_attention_grad` op was traced into a program: "saved"
    where its backward kernels read the row statistics the forward op
    kept, "recomputed" where the generic gradient ran the forward again
    to get them (a sequence-parallel op, or a program built before the
    op had the output).  One count per gradient op a lowered program
    holds."""
    _reg().counter("flash_attention_grad_lowerings_total",
                   "flash_attention_grad ops lowered, by where their "
                   "residuals came from",
                   labelnames=("residuals",)) \
          .labels(residuals=residuals).inc()


def on_moe_lowering(experts, top_k):
    """A routed expert layer (`moe_experts`, ops/moe.py) was traced
    into a program: one count per op instance a lowered program
    holds."""
    _reg().counter("moe_lowerings_total",
                   "routed expert layers lowered, by experts and experts "
                   "a token",
                   labelnames=("experts", "top_k")) \
          .labels(experts=experts, top_k=top_k).inc()


def on_moe_gmm_lowering(kernel, block_m, block_n, block_k, empty_groups):
    """One of the grouped-product kernels ("fwd", "dx", "dw":
    kernels/grouped_matmul.py) was traced into a program, with the
    tiling chosen for it and what its list of visits does with a group
    that has no row ("skipped": the row products; "visited": the weight
    gradient, which writes its zeros): one count per kernel instance a
    lowered program holds."""
    _reg().counter("moe_gmm_lowerings_total",
                   "grouped-product kernels lowered, by kernel, tiling and "
                   "whether an empty group is visited",
                   labelnames=("kernel", "block_m", "block_n", "block_k",
                               "empty_groups")) \
          .labels(kernel=kernel, block_m=block_m, block_n=block_n,
                  block_k=block_k, empty_groups=empty_groups).inc()


def on_moe_share_lowering(scored, held, top_k):
    """A routed expert layer that holds a range of the experts its
    router scores (`moe_experts` with fewer experts than the router's
    width, ops/moe.py) was traced into a program: one count per op
    instance a lowered program holds."""
    _reg().counter("moe_share_lowerings_total",
                   "expert layers lowered that hold a range of the "
                   "experts scored, by experts scored, held, and a token",
                   labelnames=("scored", "held", "top_k")) \
          .labels(scored=scored, held=held, top_k=top_k).inc()


def on_mla_cached_attention_lowering(heads, latent, rope, cache_dtype,
                                     selected="all", positions=1, tile=1):
    """A decode step of latent attention (`mla_cached_attention`,
    ops/attention.py), or a block of `positions` of them, was traced
    into a program, over every slot of its cache (`selected` "all") or
    over so many chosen ones a position, `tile` positions' sets gathered
    and attended at once: one count per op instance a lowered program
    holds."""
    _reg().counter("mla_cached_attention_lowerings_total",
                   "latent-attention decode steps lowered, by heads, "
                   "latent and rotated-key widths, the cache's type, "
                   "the slots attended (all, or so many chosen), the "
                   "positions of a row the op took and how many of them "
                   "it attends at once",
                   labelnames=("heads", "latent", "rope", "cache_dtype",
                               "selected", "positions", "tile")) \
          .labels(heads=heads, latent=latent, rope=rope,
                  cache_dtype=str(cache_dtype), selected=selected,
                  positions=positions, tile=tile).inc()


def on_mla_decode_lowering(path, block_k, positions=1):
    """Which way an `mla_cached_attention` op traced into a program
    takes over its cache: "kernel", the walk of the live slots of the
    whole extent in blocks of `block_k` (kernels/mla_decode.py);
    "kernel_chosen", the same kernel over a step's gathered set;
    or "plain", the contractions over the whole extent or a gathered
    set (`block_k` 0); and the `positions` of a row it was lowered for
    (1: a decode step; more: a block of a prompt's prefill).  One count
    per op instance a lowered program holds."""
    _reg().counter("mla_decode_lowerings_total",
                   "latent-attention decode steps lowered, by path (the "
                   "kernel over the live slots of the whole extent or "
                   "of a step's gathered set, or the plain products), "
                   "the kernel's block of slots and the positions of a "
                   "row the op took",
                   labelnames=("path", "block_k", "positions")) \
          .labels(path=path, block_k=block_k, positions=positions).inc()


def on_mla_index_select_lowering(heads, dim, top_k, cache_dtype, select,
                                 positions=1, tile=1):
    """A decode step of a chooser of cache slots (`mla_index_select`,
    ops/attention.py), or a block of `positions` of them whose scores
    are made `tile` positions at a time, was traced into a program: one
    count per op instance a lowered program holds.  `select` is how the
    op picks its slots from the scores: "count" (kernels/topk_select.py:
    the threshold by counting, the slots in slot order) or "sort"
    (`lax.top_k`, which the op no longer takes for any shape)."""
    _reg().counter("mla_index_select_lowerings_total",
                   "index-select decode steps lowered, by index heads, "
                   "their width, the slots chosen, the key cache's type, "
                   "the selection's form, the positions of a row the op "
                   "took and how many of them it scores at once",
                   labelnames=("heads", "dim", "top_k", "cache_dtype",
                               "select", "positions", "tile")) \
          .labels(heads=heads, dim=dim, top_k=top_k,
                  cache_dtype=str(cache_dtype), select=select,
                  positions=positions, tile=tile).inc()


def on_moe_grouped_router_lowering(experts, groups, kept, top_k):
    """A router whose choice is limited to the best `kept` of `groups`
    groups of its experts (`moe_router` with `n_group`, ops/moe.py) was
    traced into a program: one count per op instance a lowered program
    holds."""
    _reg().counter("moe_grouped_router_lowerings_total",
                   "group-limited routers lowered, by experts scored, "
                   "groups, groups kept and experts a token",
                   labelnames=("experts", "groups", "kept", "top_k")) \
          .labels(experts=experts, groups=groups, kept=kept,
                  top_k=top_k).inc()


def on_cached_attention_lowering(block):
    """Attention through a key/value cache (`cached_attention`,
    ops/attention.py) was traced into a program, over `block` positions
    of every row (1: a decode step): one count per op instance a lowered
    program holds."""
    _reg().counter("cached_attention_lowerings_total",
                   "key/value-cached attention ops lowered, by the "
                   "positions of a row one application takes",
                   labelnames=("block",)).labels(block=block).inc()


def _kv_cache_slots(kind, slots):
    _reg().counter("kv_cache_slots_total",
                   "slots a row's key/value caches hold in the lowered "
                   "cached_attention ops, by the kind of cache (a "
                   "window's ring, the full extent, or the full extent "
                   "under a chosen set)",
                   labelnames=("kind",)).labels(kind=kind).inc(slots)


def on_window_attention_lowering(kind, kv_heads, window, path, block_k,
                                 slots, block, step):
    """A `cached_attention` op (ops/attention.py) was traced into a
    program: over which kind of cache ("window": a ring of `window`
    slots; "full": the whole extent, `window` 0), with how many
    key/value heads, over how many positions of a row an application
    (`block`; 1: a decode step), and which way it takes over the cache
    ("kernel": the walk of the live slots in blocks of `block_k`,
    kernels/gqa_decode.py, `step` the (rows of the batch, key/value
    heads) a grid step of it takes: more than (1, 1) where one head's
    block is no step's worth of bytes; "plain": scores over every slot
    under a mask, `block_k` 0, `step` (1, 1)); and the `slots` a row of
    its cache holds, added up by kind.  One count per op instance a
    lowered program holds."""
    _reg().counter("window_attention_lowerings_total",
                   "key/value-cached attention ops lowered, by the kind "
                   "of cache (a window's ring or the full extent), "
                   "key/value heads, window, the positions of a row one "
                   "application takes, path (the kernel over the live "
                   "slots, or the plain products), the kernel's block "
                   "of slots, and the rows and key/value heads a grid "
                   "step of it takes",
                   labelnames=("kind", "kv_heads", "window", "block",
                               "path", "block_k", "step_rows",
                               "step_heads")) \
          .labels(kind=kind, kv_heads=kv_heads, window=window, block=block,
                  path=path, block_k=block_k, step_rows=step[0],
                  step_heads=step[1]).inc()
    _kv_cache_slots(kind, slots)


def on_block_causal_attention_lowering(diffusion_block, block, path):
    """A `cached_attention` op under the block-causal mask of generation
    by diffusion over blocks (its `diffusion_block` attr, B) was traced
    into a program: over `block` positions of a row an application (B: a
    pass over one block; more: a prompt's prefill, block / B whole
    blocks), by the way it takes over the cache.  The same op counts in
    `window_attention_lowerings_total` under kind "block_causal".  One
    count per op instance a lowered program holds."""
    _reg().counter("block_causal_attention_lowerings_total",
                   "key/value-cached attention ops lowered under the "
                   "block-causal mask, by the diffusion block's length, "
                   "the positions of a row one application takes, and "
                   "path",
                   labelnames=("diffusion_block", "block", "path")) \
          .labels(diffusion_block=diffusion_block, block=block,
                  path=path).inc()


def on_sparse_attention_lowering(kv_heads, top_k, slots, path, block_k,
                                 positions=1, tile=1):
    """A `cached_attention` op (ops/attention.py) was traced into a
    program over a chosen set (Selected and Live: a step, or a block of
    `positions` of them with a set each, `tile` positions' sets gathered
    and attended at once,
    over whole-extent caches of `slots` slots a row that gathers `top_k`
    of them a position for all `kv_heads` key/value heads): which way it
    takes over the gathered slots.  `path` has two values: "kernel", whole slots
    gathered with their heads side by side and
    kernels/gqa_decode.py `gqa_decode_chosen` over the copies as they
    lie, `block_k` the entries a grid step folds; "plain", a copy a
    cache with the heads apart and scores under a mask, `block_k` 0.
    (No third: a kernel that fetched the chosen slots itself was
    measured and is not built, PERF.md section 6, PR 60.)  One count
    per op instance a lowered program holds; the caches' slots go to
    `kv_cache_slots_total` under the kind "sparse"."""
    _reg().counter("sparse_attention_lowerings_total",
                   "key/value-cached attention ops over a chosen set "
                   "lowered, by key/value heads, slots chosen, the cache's "
                   "extent, path (the kernel over the gathered slots, or "
                   "the plain products), the entries a grid step of "
                   "the kernel folds, the positions of a row the op took "
                   "and how many of them it attends at once",
                   labelnames=("kv_heads", "top_k", "slots", "path",
                               "block_k", "positions", "tile")) \
          .labels(kv_heads=kv_heads, top_k=top_k, slots=slots, path=path,
                  block_k=block_k, positions=positions, tile=tile).inc()
    _kv_cache_slots("sparse", slots)


def on_sectioned_rope_lowering(heads, sections, block):
    """A `rope` op (ops/attention.py) with `sections` was traced into a
    program: three positions a token, the pairs of a head split between
    them `sections[0]` : `sections[1]` : `sections[2]`, over `block`
    positions of a row.  One count per op instance a lowered program
    holds."""
    _reg().counter("sectioned_rope_lowerings_total",
                   "rotary ops with three-part positions lowered, by "
                   "heads, the pairs a component turns and the positions "
                   "of a row one application takes",
                   labelnames=("heads", "sections", "block")) \
          .labels(heads=heads, sections="-".join(map(str, sections)),
                  block=block).inc()


def on_flash_window_lowering(kernel, window, block_q, block_k):
    """A flash-attention kernel of the training op ("fwd", or the
    backward's "dq_dkv", "ring", "dkv" or "dq") was traced into a
    program with a window: every query bounded to its last `window` keys, at the
    blocks chosen under that bound (kernels/flash_attention.py).  What
    the bound saves is in `flash_attention_pairs_total`, which counts a
    window kernel's folded and attended pairs under it.  One count per
    kernel instance a lowered program holds; none for a kernel with no
    window."""
    _reg().counter("flash_attention_window_lowerings_total",
                   "flash-attention kernels lowered with a window, by "
                   "kernel, window and tiling",
                   labelnames=("kernel", "window", "block_q", "block_k")) \
          .labels(kernel=kernel, window=window, block_q=block_q,
                  block_k=block_k).inc()


def on_moe_share_bwd_lowering(scored, held, top_k):
    """The gradient of a routed expert layer that holds a range of the
    experts its router scores (`moe_experts_grad`, ops/moe.py) was
    traced into a program: its six grouped products run over the held
    groups alone.  One count per gradient op a lowered program
    holds."""
    _reg().counter("moe_share_bwd_lowerings_total",
                   "gradients lowered of expert layers that hold a range "
                   "of the experts scored, by experts scored, held, and "
                   "a token",
                   labelnames=("scored", "held", "top_k")) \
          .labels(scored=scored, held=held, top_k=top_k).inc()


def on_moe_share_compact_lowering(rows, chunk):
    """An expert layer that holds a range of the experts scored was
    traced into a program with its row work by chunks (ops/moe.py): the
    passes between the grouped products are loops over `chunk` rows a
    trip of the `rows` assignments ordered, as many trips as hold a
    row of the held range.  One count per forward op a lowered program
    holds.  The trips are data on the device: the op's `Counts` output
    is what a caller fetches to see them (sum(Counts) // chunk + 1)."""
    _reg().counter("moe_share_compact_lowerings_total",
                   "expert layers lowered with their row work as loops "
                   "over chunks of the held rows, by assignments ordered "
                   "and a chunk's rows",
                   labelnames=("rows", "chunk")) \
          .labels(rows=rows, chunk=chunk).inc()


def on_diffusion_call(denoise_passes, commit_passes, folded_commits,
                      step_applications, tokens):
    """One `ProgramDecoder.diffuse` call has returned (generation by
    diffusion over blocks, models/decode.py `block_diffusion_decode`):
    the passes its blocks took by kind, read off the call's own results
    ("denoise": a pass that fixed positions of a block by confidence and
    stored nothing for good; "commit": the step over a block's final
    tokens, whose keys and values the cache keeps: one a block wherever
    it ran, so they count the blocks committed too) and the tokens it
    generated (rows x the generated length).  Tokens over passes is what
    a pass yields: none, one or several a row.  What the passes cost is
    counted apart: `folded_commits` of the commits rode on the next
    block's first denoising pass, and the step was applied
    `step_applications` times after the prefill."""
    reg = _reg()
    family = reg.counter("decoder_diffusion_passes_total",
                         "passes of block-diffusion generation calls, by "
                         "kind (denoise: fixes positions, stores nothing; "
                         "commit: writes the cache)", labelnames=("kind",))
    family.labels(kind="denoise").inc(denoise_passes)
    family.labels(kind="commit").inc(commit_passes)
    reg.counter("decoder_diffusion_blocks_total",
                "blocks block-diffusion generation calls committed") \
       .inc(commit_passes)
    reg.counter("decoder_diffusion_folded_commits_total",
                "commits of block-diffusion generation calls that rode on "
                "the next block's first denoising pass").inc(folded_commits)
    reg.counter("decoder_diffusion_applications_total",
                "applications of the step block-diffusion generation "
                "calls made after their prefills (a denoising pass each, "
                "and a commit that rode on none)").inc(step_applications)
    reg.counter("decoder_diffusion_tokens_total",
                "tokens block-diffusion generation calls generated (rows "
                "x generated length)").inc(tokens)


def on_prefill_lowering(form, block):
    """A prompt's prefill (models/decode.py `prefill`) was traced into a
    program: as a scan of the one-token step ("step", `block` 1), or in
    blocks of at most `block` positions through a step that takes a
    block ("block").  One count per prefill a lowered program holds."""
    _reg().counter("prefill_lowerings_total",
                   "prompt prefills lowered, by form (a scan of one-token "
                   "steps, or of blocks of positions) and block length",
                   labelnames=("form", "block")) \
          .labels(form=form, block=block).inc()


def on_ssd_lowering(kernel, chunk, heads_per_step):
    """The chunked state-space scan ("fwd") or its gradient ("bwd":
    kernels/ssd.py) was traced into a program, with its chunk and the
    heads a grid step takes: one count per instance a lowered program
    holds.  A gradient op that ran the forward again would count a
    "fwd" of its own."""
    _reg().counter("ssd_lowerings_total",
                   "chunked state-space scans lowered, by kernel, chunk "
                   "and heads a grid step",
                   labelnames=("kernel", "chunk", "heads_per_step")) \
          .labels(kernel=kernel, chunk=chunk,
                  heads_per_step=heads_per_step).inc()


def on_causal_conv1d_lowering(width, activation):
    """A causal depthwise convolution (`causal_conv1d`, ops/ssm.py) was
    traced into a program: one count per op instance a lowered program
    holds."""
    _reg().counter("causal_conv1d_lowerings_total",
                   "causal depthwise convolutions lowered, by width and "
                   "activation",
                   labelnames=("width", "activation")) \
          .labels(width=width, activation=activation).inc()


def _recurrent_state_bytes(kind, row_bytes):
    _reg().counter("recurrent_state_bytes_total",
                   "bytes of recurrent state a row holds in the lowered "
                   "ops that take a state in and hand it on, by kind (a "
                   "delta rule's state, a convolution's tail, a "
                   "selective scan's state, a Mamba-2 scan's)",
                   labelnames=("kind",)).labels(kind=kind).inc(row_bytes)


def on_causal_conv1d_tail_lowering(width, row_bytes):
    """Beside `on_causal_conv1d_lowering`, for a convolution that is
    handed the `width - 1` positions before its block and hands on its
    own (a cached step's; `row_bytes` a row's tail): one count per op
    instance a lowered program holds.  A convolution that starts from
    zeros counts nothing here."""
    _reg().counter("causal_conv1d_tail_lowerings_total",
                   "causal depthwise convolutions lowered that carry the "
                   "tail of the positions before their block, by width",
                   labelnames=("width",)).labels(width=width).inc()
    _recurrent_state_bytes("conv_tail", row_bytes)


def on_gated_delta_rule_lowering(form, path, chunk, heads, state_dtype,
                                 row_bytes, gate="head", key_dim=0,
                                 value_dim=0):
    """A `gated_delta_rule` op (ops/linear_attention.py) was traced into
    a program: in which form ("step": one position, the state read and
    written once; "block": chunks of `chunk` positions), which way
    ("kernel": kernels/gdn_step.py; "plain": `jax.numpy`), over how many
    value heads and a state of which type and shape a head (`key_dim` x
    `value_dim`: a reader tells a 96 x 192 instance from a 128 x 128
    one, and `path="plain"` at a shape the kernel is to take is a
    fault it can see); `row_bytes` the state a row holds; `gate` "head"
    (one decay a head: Gated DeltaNet) or "channel" (one a key channel:
    KDA).  One count per op instance a lowered program holds."""
    _reg().counter("gated_delta_rule_lowerings_total",
                   "gated delta rule ops lowered, by form (a step or a "
                   "block of chunks), path (the step kernel or plain "
                   "products), chunk, value heads, the state's type, "
                   "the gate (a head's or a key channel's) and a head's "
                   "state (key_dim x value_dim)",
                   labelnames=("form", "path", "chunk", "heads",
                               "state_dtype", "gate", "key_dim",
                               "value_dim")) \
          .labels(form=form, path=path, chunk=chunk, heads=heads,
                  state_dtype=str(state_dtype), gate=gate, key_dim=key_dim,
                  value_dim=value_dim).inc()
    _recurrent_state_bytes("delta", row_bytes)


def on_selective_scan_lowering(form, state_dtype, row_bytes):
    """A `selective_scan` op (ops/ssm.py: Mamba-1's recurrence with its
    state handed in and on) was traced into a program: in which form
    ("step": one position, the state read and written once; "block":
    the positions of a block walked through that same update) and with
    a state of which type; `row_bytes` the state a row holds.  One count
    per op instance a lowered program holds."""
    _reg().counter("selective_scan_lowerings_total",
                   "selective state-space scans lowered that carry "
                   "their state, by form (a step or a block of "
                   "positions) and the state's type",
                   labelnames=("form", "state_dtype")) \
          .labels(form=form, state_dtype=str(state_dtype)).inc()
    _recurrent_state_bytes("ssm", row_bytes)


def on_ssd_scan_lowering(form, path, chunk, heads, state_dtype, row_bytes):
    """An `ssd_scan` op that carries its state (ops/ssm.py: Mamba-2's
    recurrence with `State`) was traced into a program: in which form
    ("step": one position, the state read and written once; "block":
    whole chunks of `chunk` positions), which way ("kernel":
    kernels/ssd.py's `ssd_block_*`; "plain": `jax.numpy`), over how many
    heads and carried states of which type; `row_bytes` the state a row
    hands on.  One count per op instance a lowered program holds.
    Training's form, which carries none, is counted by its kernels
    (`on_ssd_lowering`)."""
    _reg().counter("ssd_scan_lowerings_total",
                   "Mamba-2 scan ops lowered that carry their state, by "
                   "form (a step or a block of chunks), path (a kernel "
                   "or plain products), chunk, heads and the state's type",
                   labelnames=("form", "path", "chunk", "heads",
                               "state_dtype")) \
          .labels(form=form, path=path, chunk=chunk, heads=heads,
                  state_dtype=str(state_dtype)).inc()
    _recurrent_state_bytes("ssd", row_bytes)


def on_decoder_positions(part, positions):
    """A step Program with a self-decoder and a cross-decoder was
    lowered: the positions of a row an application runs through `part`
    ("self": the layers that hold state, counted by the one op that
    writes the cache the cross-decoder reads; "cross": the layers that
    read it and hold none, counted by the first of its readers).  A
    block of 128 positions whose cross-decoder runs at its last alone
    counts 128 and 1, a decode step 1 and 1."""
    _reg().counter("decoder_positions_total",
                   "positions of a row that the lowered step programs "
                   "run through their self-decoder and through their "
                   "cross-decoder, an application",
                   labelnames=("part",)).labels(part=part).inc(positions)


def on_cached_attention_readonly_lowering(reader, block, path, block_k):
    """A `cached_attention` op without KNew / VNew was traced into a
    program: it reads a cache another op of the step wrote and writes
    none, over `block` positions of every row, by `path` ("kernel":
    kernels/gqa_decode.py over blocks of `block_k` slots; "plain");
    `reader` is which of the cache's readers it is, as its builder
    numbered them from 1 (0: not said).  One count per op instance a
    lowered program holds; the first reader also counts the
    cross-decoder's positions (`on_decoder_positions`).  It holds no
    cache, so `kv_cache_slots_total` counts nothing for it."""
    _reg().counter("cached_attention_readonly_lowerings_total",
                   "key/value-cached attention ops lowered that read a "
                   "cache another op wrote and write none, by the "
                   "positions of a row one application takes, path and "
                   "the kernel's block of slots",
                   labelnames=("block", "path", "block_k")) \
          .labels(block=block, path=path, block_k=block_k).inc()
    if reader == 1:
        on_decoder_positions("cross", block)


def on_shared_cache_readers(program, readers):
    """A cached step was built in which `readers` attention layers
    attend one layer's key/value cache (the layer that writes it among
    them; `models/sambay_program.py`): counted at build, as
    `on_shared_parameter_uses` is."""
    _reg().counter("program_shared_cache_readers",
                   "attention layers of a built step that attend one "
                   "layer's key/value cache, its writer among them",
                   labelnames=("program",)) \
          .labels(program=str(program._cache_token)).inc(readers)


def on_shared_parameter_uses(program, uses):
    """`append_backward` found parameters that more than one op reads
    (weights shared across depth): `uses` gradient contributions a step
    are summed into their gradients, one `sum` op per such parameter."""
    _reg().counter("program_shared_parameter_uses",
                   "gradient contributions summed per step for "
                   "parameters that several ops read",
                   labelnames=("program",)) \
          .labels(program=str(program._cache_token)).inc(uses)


def on_index_sets_reused(program, reused):
    """A cached step was built whose `reused` attention layers hold no
    chooser and attend the set a layer below them chose
    (`models/latent_moe_program.py`, `indexer_types`): counted at build,
    once a layer that inherits its set."""
    _reg().counter("program_index_sets_reused",
                   "attention layers of a built step that attend the "
                   "chosen set of a layer below them",
                   labelnames=("program",)) \
          .labels(program=str(program._cache_token)).inc(reused)


def on_transfer(direction, nbytes):
    """Host<->device bytes moved by the executor feed/fetch paths.
    direction: "h2d" (feeds placed on device) or "d2h" (fetches pulled
    to host)."""
    if nbytes:
        _reg().counter("executor_transfer_bytes_total",
                       "host<->device bytes moved by executor "
                       "feed/fetch", labelnames=("direction",)) \
              .labels(direction=direction).inc(int(nbytes))


def on_decoder_call(mode, built, rows, prompt_len, max_len, host_bytes,
                    device_bytes, seconds):
    """One public call of `fluid.ProgramDecoder` (`mode`: "greedy",
    "greedy-prefill", "sample", "beam") has returned: whether it made a
    new entry of the decoder's compiled programs (`built`: each a trace
    and a compile), the tokens it was asked for (`rows` x `prompt_len`
    of prompt, `rows` x `max_len` generated: host arithmetic, not what
    came before an eos), the bytes of `init_state` it was handed by
    where they were (`host_bytes` as host arrays, `device_bytes` as
    `jax.Array`s), and `seconds`, the call's (prep, dispatch, fetch)
    intervals, the `decode/*` spans' own, which record nothing without a
    profiler session.  What `on_transfer` is for the executor."""
    reg = _reg()
    reg.counter("decoder_calls_total", "ProgramDecoder calls, by mode",
                labelnames=("mode",)).labels(mode=mode).inc()
    if built:
        reg.counter("decoder_programs_total",
                    "programs ProgramDecoder built (a trace and a compile "
                    "each: one a mode, lengths and batch), by mode",
                    labelnames=("mode",)).labels(mode=mode).inc()
    for name, label, text, amounts in (
            ("decoder_tokens_total", "kind",
             "tokens ProgramDecoder calls were asked for: rows x prompt "
             "length, rows x generated length",
             (("prompt", rows * prompt_len), ("generated", rows * max_len))),
            ("decoder_state_bytes_total", "source",
             "bytes of init_state ProgramDecoder calls were handed, by "
             "where they were (host arrays, or jax.Arrays on the device)",
             (("host", host_bytes), ("device", device_bytes))),
            ("decoder_seconds_total", "phase",
             "seconds inside ProgramDecoder calls, by phase (prep: "
             "validation and the state's and prompt's way to the device; "
             "dispatch: the jitted call until it returns; fetch: the wait "
             "for the device and the results' way to the host)",
             zip(("prep", "dispatch", "fetch"), seconds))):
        family = reg.counter(name, text, labelnames=(label,))
        for value, amount in amounts:
            if amount:
                family.labels(**{label: value}).inc(amount)


def transfer_bytes(direction):
    fam = _reg().counter("executor_transfer_bytes_total",
                         "host<->device bytes moved by executor "
                         "feed/fetch", labelnames=("direction",))
    return fam.labels(direction=direction).value


# ---------------------------------------------------------------------------
# jit phases
# ---------------------------------------------------------------------------

_JIT_PHASES = {
    "/jax/core/compile/jaxpr_trace_duration": "trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
    "/jax/core/compile/backend_compile_duration": "compile",
}


def _on_jit_phase(event, duration, fun_name="", **_):
    """`jit_phase_seconds_total{phase, fun_name}`: JAX reports the
    python function's name for the trace and `jit(<name>)` for the
    other two; one label value serves all three (the executor's
    function is `segment_fn`, the trainers' is `step`).  A persistent
    cache hit is a `compile` of its load time.  Fires only when
    something compiles, and is told a phase when it has ended: the
    start-up timeline gets it as `startup/jit_<phase>` from `duration`
    ago, under the start-up event open on this thread (the first run
    that made JAX compile; none for a jit of the caller's own)."""
    phase = _JIT_PHASES.get(event)
    if phase is None:
        return
    if fun_name.startswith("jit(") and fun_name.endswith(")"):
        fun_name = fun_name[len("jit("):-1]
    trace_mod.emit_span("startup/jit_" + phase,
                        time.perf_counter() - duration, duration,
                        cat=trace_mod.STARTUP,
                        args={"fun_name": fun_name})
    _reg().counter("jit_phase_seconds_total",
                   "seconds JAX spent tracing, lowering and compiling "
                   "(or loading from the persistent cache), per jitted "
                   "function", labelnames=("phase", "fun_name")) \
          .labels(phase=phase, fun_name=fun_name).inc(duration)


jax.monitoring.register_event_duration_secs_listener(_on_jit_phase)


_COMPILE_CACHE_EVENTS = {
    "/jax/compilation_cache/cache_hits": (
        "compile_cache_hits_total",
        "executables JAX's persistent compilation cache loaded from "
        "disk"),
    "/jax/compilation_cache/cache_misses": (
        "compile_cache_misses_total",
        "executables compiled and written to JAX's persistent "
        "compilation cache"),
}


def _on_compile_cache_event(event, **_):
    """`compile_cache_{hits,misses}_total`: what JAX's persistent
    compilation cache (`utils/compile_cache.py`) reports.  Both stay 0
    while the cache is off."""
    counter = _COMPILE_CACHE_EVENTS.get(event)
    if counter is not None:
        _reg().counter(*counter).inc()


jax.monitoring.register_event_listener(_on_compile_cache_event)


# ---------------------------------------------------------------------------
# trainer-side hooks
# ---------------------------------------------------------------------------

class _StepTimer:
    """Times one training step inside a `<trainer>/step` span, in
    which whatever the step runs nests, and feeds the trainer metric
    family.  `examples` may be set after entry, by a step that learns
    its batch size from its feeds."""

    __slots__ = ("trainer", "examples", "args", "t0", "_dt", "_span")

    def __init__(self, trainer, examples, args):
        self.trainer = trainer
        self.examples = examples
        self.args = args
        self._dt = None

    def __enter__(self):
        self._span = trace_mod.span(self.trainer + "/step",
                                    cat="trainer", **self.args)
        self._span.__enter__()
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, exc_type, exc, tb):
        if exc_type is None and self._dt is None:
            self.record()
        self._span.__exit__(exc_type, exc, tb)
        return False

    def record(self):
        """Feed the trainer metrics with the time since entry.  Exit
        does it; a step with work of its own after the timed part (its
        monitor, its flight record, which reads these counters) calls
        it there, inside its span, and exit does not count it twice."""
        self._dt = dt = time.perf_counter() - self.t0
        reg = _reg()
        reg.counter("trainer_steps_total", "completed train steps",
                    labelnames=("trainer",)) \
           .labels(trainer=self.trainer).inc()
        reg.histogram("trainer_step_seconds", STEP_SECONDS_BUCKETS,
                      "train step wall time",
                      labelnames=("trainer",)) \
           .labels(trainer=self.trainer).observe(dt)
        if self.examples:
            reg.counter("trainer_examples_total",
                        "examples consumed by train steps",
                        labelnames=("trainer",)) \
               .labels(trainer=self.trainer).inc(self.examples)
            if dt > 0:
                reg.gauge("trainer_examples_per_sec",
                          "throughput of the most recent step",
                          labelnames=("trainer",)) \
                   .labels(trainer=self.trainer) \
                   .set(self.examples / dt)


def step(trainer, examples=None, **args):
    """`with telemetry.step("v2", examples=len(batch)): run_step()` —
    times the step inside a `<trainer>/step` span, feeds the trainer
    metrics."""
    return _StepTimer(trainer, examples, args)


def set_gauge(name, value, **labels):
    """Set a named gauge (loss, loss scale, grad norm, ...).  Labeled
    when label kwargs are given."""
    reg = _reg()
    if labels:
        g = reg.gauge(name, labelnames=tuple(sorted(labels)))
        g.labels(**labels).set(value)
    else:
        reg.gauge(name).set(value)


def _flat_samples():
    """One (key, sample) pair per registry sample, with the
    `name{k=v,...}` key convention shared by snapshot/snapshot_delta
    (kept in ONE place so the two views can't drift apart)."""
    for s in _reg().to_dict()["metrics"]:
        key = s["name"]
        labels = s.get("labels")
        if labels:
            key += "{%s}" % ",".join(
                "%s=%s" % (k, v) for k, v in sorted(labels.items()))
        yield key, s


def snapshot():
    """Flat {metric_name or name{labels}: value} view of the default
    registry (histograms contribute _count/_sum) — for embedding
    registry state into artifacts or asserting on it in tests.  This
    is the flight recorder's per-step delta base, so those artifacts
    carry the full registry (including the per-segment xla_*
    memory/cost gauges)."""
    return snapshot_and_delta({})[0]


def snapshot_and_delta(before):
    """(snapshot(), snapshot_delta(before)) from ONE registry walk —
    for per-step callers (the flight recorder) that need both the new
    baseline and the movement and shouldn't serialize the registry
    twice per training step."""
    snap, delta = {}, {}
    for key, s in _flat_samples():
        if s["type"] == "histogram":
            cnt, tot = s["count"], round(s["sum"], 6)
            snap[key + "_count"] = cnt
            snap[key + "_sum"] = tot
            if cnt != before.get(key + "_count", 0):
                delta[key + "_count"] = cnt - before.get(key + "_count",
                                                         0)
                delta[key + "_sum"] = round(
                    tot - before.get(key + "_sum", 0), 6)
        elif s["type"] == "counter":
            snap[key] = s["value"]
            if s["value"] != before.get(key, 0):
                delta[key] = s["value"] - before.get(key, 0)
        else:
            snap[key] = s["value"]
            if s["value"] != before.get(key):
                delta[key] = s["value"]
    return snap, delta


def snapshot_delta(before):
    """The registry's movement since `before` (a `snapshot()` result):
    counters and histogram _count/_sum report the INCREMENT over the
    window, gauges their current value; keys that didn't move are
    dropped.  This is the honest per-window attribution — a cumulative
    snapshot stamped onto one bench leg or flight-recorder step would
    claim every previous window's counters as its own."""
    return snapshot_and_delta(before)[1]
