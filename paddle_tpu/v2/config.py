"""v2 process-level config state (reference: the gflags handled by
python/paddle/v2/__init__.py init)."""

_state = {"initialized": False, "use_tpu": None, "trainer_count": 1}


def init(use_gpu=None, use_tpu=None, trainer_count=1, **kwargs):
    """`use_tpu=False` (or the reference's spelling, `use_gpu=False`)
    keeps the process on the host CPU; left unset, placement follows
    JAX's default backend."""
    _state["initialized"] = True
    _state["use_tpu"] = use_tpu if use_tpu is not None else use_gpu
    _state["trainer_count"] = trainer_count


def _place():
    from .. import fluid

    if _state["use_tpu"] is None or _state["use_tpu"]:
        return fluid.TPUPlace(0)
    return fluid.CPUPlace()
