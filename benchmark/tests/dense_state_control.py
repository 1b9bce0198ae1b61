"""The controls of the dense state cell's `correct`, at the cell's own
size on the chip or at a toy size under pytest (test_olmohybrid_cell.py):
benchmark/tests/state_control.py, which serves one call of a cell a seed
and holds it to the reference made wrong in one named way a `--control`,
with this cell's list under `--all`
(benchmark/reference/olmo_hybrid.py lists what each switches).

    python3 benchmark/tests/dense_state_control.py \
        --workload olmohybrid-decode-pp4 --seeds 11,12 --all

`--all`: the state rounded to bfloat16 after every position, beta not
doubled, the decay left out, the tail not carried across the
prefill/decode boundary, the state zeroed after every position, the
pre-norm reading of the block, rotation at the family's theta 500,000, q
and k normed head by head, sigmoid(z) for silu(z) on the rule's output,
no q/k norm.  Exits 1 unless the sound line passes every limit and every
control is refused by one.
"""

import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(os.path.dirname(HERE)))

from benchmark.tests import state_control  # noqa: E402


def controls_of(config, workload):
    """{spelling: the reference's `control`} of `--all`."""
    return {
        "state=bfloat16": {"state": "bfloat16"},
        "beta_scale=1": {"beta_scale": 1},
        "decay=false": {"decay": False},
        "tail_cut=%d" % workload["prompt_len"]:
            {"tail_cut": workload["prompt_len"]},
        "state=zero": {"state": "zero"},
        "norm_order=pre": {"norm_order": "pre"},
        "rotary=500000": {"rotary": 500000},
        "qk_norm=head": {"qk_norm": "head"},
        "z_gate=sigmoid": {"z_gate": "sigmoid"},
        "qk_norm=none": {"qk_norm": "none"},
    }


def main(argv=None):
    state_control.controls_of = controls_of
    return state_control.main(argv)


if __name__ == "__main__":
    sys.exit(main())
