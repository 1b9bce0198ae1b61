"""The controls of the Mamba-2 state cell's `correct`, at the cell's own
size on the chip or at a toy size under pytest
(test_granite_decode_cell.py): benchmark/tests/state_control.py, which
serves one call of a cell a seed and holds it to the reference made
wrong in one named way a `--control`, with this cell's list under
`--all` (benchmark/reference/granite_moe_hybrid.py lists what each
switches).

    python3 benchmark/tests/ssd_state_control.py \
        --workload granite-decode-ep4 --seeds 11,12 --all

`--all`: the state rounded to bfloat16 after every position, the decay
left out, the state not carried across the prefill/decode border, the
tail not carried across it, `D x` left out, the softmax scaled by
head_dim ** -0.5, the residual multiplier taken as 1, the shared expert
at the routed experts' width, a token's last held expert dropped.  Exits
1 unless the sound line passes every limit and every control is refused
by one.
"""

import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(os.path.dirname(HERE)))

from benchmark.tests import state_control  # noqa: E402


def controls_of(config, workload):
    """{spelling: the reference's `control`} of `--all`."""
    border = workload["prompt_len"]
    return {
        "state=bfloat16": {"state": "bfloat16"},
        "decay=false": {"decay": False},
        "state_cut=%d" % border: {"state_cut": border},
        "tail_cut=%d" % border: {"tail_cut": border},
        "skip=false": {"skip": False},
        "attention_multiplier=head_dim**-0.5":
            {"attention_multiplier": config["head_dim"] ** -0.5},
        "residual_multiplier=1": {"residual_multiplier": 1.0},
        "shared_width=%d" % config["intermediate_size"]:
            {"shared_width": config["intermediate_size"]},
        "drop=true": {"drop": True},
    }


def main(argv=None):
    state_control.controls_of = controls_of
    return state_control.main(argv)


if __name__ == "__main__":
    sys.exit(main())
