"""The share of a block-diffusion call's passes that commit: a block's
last pass, over its final tokens, whose keys and values the cache keeps
and whose logits nothing reads
(`decoder_diffusion_passes_total{kind=commit}` over both kinds, the
program's counters over every call of the process).  One of T + 1 at the
default's floor (20% at T = 4); the pass a later PR could fold into the
next block's first denoising pass (ROADMAP)."""

LAYER = "decoding"
MOVES = "decode_tok_per_s"
UNIT = "%"
SOURCE = "program_counter"


def read(run):
    found = run.lookup.module("layer_metrics",
                              "diffusion_tokens_per_pass").counted()
    if found is None or "diffusion_batch" not in run.facts:
        return None
    _, passes, _ = found
    return 100.0 * passes.get("commit", 0) / sum(passes.values())
