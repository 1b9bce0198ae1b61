"""Device time of a traced call of `sdar-diffuse-pp8` by the scope a pass
runs under and the operation: a block's first pass (`diffusion_fold`), its
later passes (`diffusion_denoise`), the rule (`diffusion_unmask`; a first
pass's and a later pass's apart, by the loops they stand in), the last
commit (`diffusion_commit`) and the prefill, each with its kernels
(`moe_gmm_fwd_*`, `gqa_decode_*`) and the Program's ops by type: seconds
a call, calls, milliseconds each.  The benchmark's readers divide a call
by its counted passes; this cuts it by application, which is how PERF.md
section 5 prices a first pass against a later one (PR 74).  Beside the
split, the float32 arrays with the vocabulary's extent that the traced
operations write, read from their own HLO text: none since the rule
reduces the step's logits where they lie, [rows x T, vocab] in the
step's type (PR 75), so the count says whether that engaged.

Run on the chip after a `--trace 1` run of the cell, in the same
checkout (the recording has to hold one traced call):

    python3 benchmark/run.py --workload sdar-diffuse-pp8 --seed 1 --seconds 20 --trace 1
    python3 scripts/diffusion_trace_split.py [trace_dir [vocabulary]]
"""

import collections
import os
import re
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# innermost first: the rule and a first pass lie inside `diffusion_denoise`
SCOPES = (("diffusion_fold", "first pass"), ("diffusion_unmask", "rule"),
          ("diffusion_denoise", "later pass"), ("diffusion_commit", "commit"),
          ("decode_prefill", "prefill"), ("decode_steps", "steps, no pass"))
KERNELS = ("moe_gmm_fwd_m256_n256_k2048", "moe_gmm_fwd_m256_n2048_k768",
           "gqa_decode")
_OP = re.compile(r"/([a-z_0-9]+)/~")
VOCAB = 151936      # benchmark/configs/sdar-30b-a3b-chat.json `vocab_size`


def split(trace_dir, ordinal=0, vocab=VOCAB):
    """({(scope, operation): [seconds, calls]}, {(operation, a float32
    array of two axes or more that it writes, the last `vocab` long):
    [seconds, calls]}) of one device's work."""
    from benchmark.reduce import decoder_trace, op_instances, op_scopes, \
        xplane

    found = collections.defaultdict(lambda: [0.0, 0])
    wide = collections.defaultdict(lambda: [0.0, 0])
    float32 = re.compile(r"f32\[(?:\d+,)+%d\]" % vocab)
    for op in decoder_trace._operations(trace_dir, ordinal):
        if op.category in xplane.CONTAINERS:
            continue
        written = op_instances.RESULT.match(op.text)
        for array in float32.findall(written.group(1) if written else ""):
            entry = wide[op.name, array]
            entry[0] += op.end - op.start
            entry[1] += 1
        parts = op_scopes.components(op.path)
        where = next((name for scope, name in SCOPES if scope in parts),
                     "no scope")
        if where == "rule":
            # a block's first pass stands in the scan of blocks' body,
            # its later passes one `while` deeper
            where = "rule, a %s pass" % (
                "later" if parts.count("while") > 1 else "first")
        kind = next((k for k in KERNELS if op.name.startswith(k)), None)
        if kind is None:
            of = _OP.search(op.path)
            # under no op of the Program (the rule), the instruction's
            # own name: `iota_reduce_fusion`, `exponential_reduce_fusion`
            kind = "%s: %s" % (of.group(1) if of else
                               re.sub(r"[.\d]+$", "", op.name), op.category)
        entry = found[where, kind]
        entry[0] += op.end - op.start
        entry[1] += 1
    return found, wide


def main(argv=None):
    argv = sys.argv[1:] if argv is None else argv
    found, wide = split(
        argv[0] if argv else ".bench_work/sdar-diffuse-pp8/trace",
        vocab=int(argv[1]) if argv[1:] else VOCAB)
    total = collections.Counter()
    for (where, _), (seconds, _) in found.items():
        total[where] += seconds
    for where, seconds in total.most_common():
        print("== %s %.4f s" % (where, seconds))
        rows = sorted(((s, n, kind) for (w, kind), (s, n) in found.items()
                       if w == where), reverse=True)
        for s, n, kind in rows[:14]:
            print("   %-48s %9.4f s  x%-6d %8.4f ms each"
                  % (kind, s, n, s / n * 1e3))
    print("== float32 arrays of the vocabulary's extent written: %d"
          % len(wide))
    for (name, array), (s, n) in sorted(wide.items()):
        print("   %-48s %9.4f s  x%-6d %s" % (name, s, n, array))
    return 0


if __name__ == "__main__":
    sys.exit(main())
