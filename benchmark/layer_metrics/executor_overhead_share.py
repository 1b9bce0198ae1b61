"""The share of a window step that `fluid.Executor.run` (path A) adds
over the same step through FunctionalProgram (path B):
1 - functional_step_ms / the untraced window's ms per step."""

LAYER = "executor"
MOVES = "train_items_per_s"
UNIT = "%"
SOURCE = "host_clock"


def read(run):
    facts = run.facts
    if run.peaks is None or "functional_step_ms" not in facts:
        return None
    return 100.0 * (1.0 - facts["functional_step_ms"] / facts["step_ms"])
