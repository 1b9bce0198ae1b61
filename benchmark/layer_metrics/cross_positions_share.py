"""The positions a prefill block runs through the cross-decoder, as a
share of those it runs through the self-decoder, from the program's
`decoder_positions_total{part=self|cross}`, which count both where a
step Program is lowered: 1 / T where a block of T positions keeps its
last position alone past the layer that writes the shared cache (0.78%
at 128), 100% where every position goes through every layer.  A step
form (one position) counts 1 and 1; `selective_scan_lowerings_total`
says how many of the lowerings were step forms (one count a Mamba layer
and lowering), and they are taken off both parts."""

from benchmark.flops import yoco

LAYER = "decoding"
MOVES = "decode_tok_per_s"
UNIT = "%"
SOURCE = "program_counter"


def _count(counters, family, label):
    """The sum of `family`'s samples that carry `label` ("name=value")."""
    return sum(value for key, value in counters.items()
               if key.startswith(family + "{")
               and label in key[len(family) + 1:-1].split(","))


def read(run):
    from paddle_tpu.obs import telemetry

    counters = telemetry.snapshot()
    positions = {part: _count(counters, "decoder_positions_total",
                              "part=" + part) for part in ("self", "cross")}
    forms = {form: _count(counters, "selective_scan_lowerings_total",
                          "form=" + form) for form in ("step", "block")}
    if run.peaks is None or "yoco_batch" not in run.facts \
            or not positions["self"] or not forms["block"]:
        return None
    layers = yoco.count(run.config, yoco.MAMBA)
    steps, blocks = (forms[form] / layers for form in ("step", "block"))
    ran = {part: positions[part] - steps for part in ("self", "cross")}
    print("lowered: %d block form(s) and %d step form(s); a block ran %.1f "
          "positions through the self-decoder and %.1f through the "
          "cross-decoder"
          % (blocks, steps, ran["self"] / blocks, ran["cross"] / blocks),
          flush=True)
    return 100.0 * ran["cross"] / ran["self"]
