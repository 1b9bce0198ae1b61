"""Device time of a traced call of `sdar-diffuse-pp8` by the scope a pass
runs under and the operation: a block's first pass (`diffusion_fold`), its
later passes (`diffusion_denoise`), the rule (`diffusion_unmask`), the
last commit (`diffusion_commit`) and the prefill, each with its kernels
(`moe_gmm_fwd_*`, `gqa_decode_*`) and the Program's ops by type: seconds
a call, calls, milliseconds each.  The benchmark's readers divide a call
by its counted passes; this cuts it by application, which is how PERF.md
section 5 prices a first pass against a later one (PR 74).

Run on the chip after a `--trace 1` run of the cell, in the same
checkout (the recording has to hold one traced call):

    python3 benchmark/run.py --workload sdar-diffuse-pp8 --seed 1 --seconds 20 --trace 1
    python3 scripts/diffusion_trace_split.py [trace_dir]
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


def split(trace_dir, ordinal=0):
    """{(scope, operation): [seconds, calls]} of one device's work."""
    from benchmark.reduce import decoder_trace, op_scopes, xplane

    found = collections.defaultdict(lambda: [0.0, 0])
    for op in decoder_trace._operations(trace_dir, ordinal):
        if op.category in xplane.CONTAINERS:
            continue
        parts = op_scopes.components(op.path)
        where = next((name for scope, name in SCOPES if scope in parts),
                     "no scope")
        kind = next((k for k in KERNELS if op.name.startswith(k)), None)
        if kind is None:
            of = _OP.search(op.path)
            kind = "%s: %s" % (of.group(1) if of else "-", op.category)
        entry = found[where, kind]
        entry[0] += op.end - op.start
        entry[1] += 1
    return found


def main(argv=None):
    argv = sys.argv[1:] if argv is None else argv
    found = split(argv[0] if argv
                  else ".bench_work/sdar-diffuse-pp8/trace")
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
    return 0


if __name__ == "__main__":
    sys.exit(main())
