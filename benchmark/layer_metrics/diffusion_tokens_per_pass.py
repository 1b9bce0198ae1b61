"""Tokens a row a pass of generation by diffusion over blocks yields:
the program's own counters, `decoder_diffusion_tokens_total` over
`decoder_diffusion_passes_total` (both kinds) over the cell's rows, over
every call of the process.  A lockstep autoregressive step yields 1; the
cell's default (4 denoising passes and a commit a block of 4) 0.8."""

from benchmark.reduce import decoder_trace

LAYER = "decoding"
MOVES = "decode_tok_per_s"
UNIT = "tok/pass"
SOURCE = "program_counter"


def counted():
    """(tokens, {kind: passes}, blocks) of the process, or None."""
    found = decoder_trace.counters()
    passes = decoder_trace.labelled(found, "decoder_diffusion_passes_total",
                                    "kind")
    if not passes or not sum(passes.values()):
        return None
    return (found.get("decoder_diffusion_tokens_total", 0), passes,
            found.get("decoder_diffusion_blocks_total", 0))


def read(run):
    found = counted()
    if found is None or "diffusion_batch" not in run.facts:
        return None
    tokens, passes, blocks = found
    each = tokens / sum(passes.values()) / run.facts["diffusion_batch"]
    print("diffusion: %d tokens in %d passes (%s) of %d blocks over %d "
          "rows: %.4f tokens a row a pass"
          % (tokens, sum(passes.values()),
             ", ".join("%s %d" % item for item in sorted(passes.items())),
             blocks, run.facts["diffusion_batch"], each), flush=True)
    return each
