"""The controls of the block-diffusion cell's `correct`, at the cell's
own size on the chip or at a toy size under pytest
(test_sdar_diffuse_cell.py): benchmark/tests/state_control.py, which
serves one call of a cell a seed and holds it to the reference made
wrong in one named way a `--control`, with this cell's list under
`--all` (benchmark/reference/sdar_moe.py lists what each switches).

    python3 benchmark/tests/diffusion_control.py \
        --workload sdar-diffuse-pp8 --seeds 11,12 --all

`--all`: (a) the causal mask inside a block; (b) no commit pass, the
last denoising pass's keys and values kept; (c) the prompt prefilled
under a plain causal mask; (d) keys and values kept in float8_e4m3fn.
Exits 1 unless the sound line passes every limit and every control is
refused by one.
"""

import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(os.path.dirname(HERE)))

from benchmark.tests import state_control  # noqa: E402


def controls_of(config, workload):
    """{spelling: the reference's `control`} of `--all`."""
    size = config["generation"]["block_length"]
    return {
        "causal_in_block=true": {"causal_in_block": True},
        "no_commit=true": {"no_commit": True},
        "causal_prefill=%d" % (workload["prompt_len"] // size * size):
            {"causal_prefill": workload["prompt_len"] // size * size},
        "kv_dtype=float8_e4m3fn": {"kv_dtype": "float8_e4m3fn"},
    }


def main(argv=None):
    state_control.controls_of = controls_of
    return state_control.main(argv)


if __name__ == "__main__":
    sys.exit(main())
