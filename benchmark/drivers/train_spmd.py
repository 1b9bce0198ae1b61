"""A training cell across chips: `spmd.SpmdTrainer` over
`make_mesh(n_devices=chips)`, the global batch sharded over the mesh with
`batch_spec`, as chip_smoke.multichip drives it.  The reference check is
made on the first global batch, on which the reference's batch statistics
span every image: that is the check that sharding changed no arithmetic.
"""

from benchmark import training


def run(run):
    import jax
    from jax.sharding import NamedSharding
    from paddle_tpu.parallel import make_mesh
    from paddle_tpu.parallel.sharding import batch_spec
    from paddle_tpu.spmd import SpmdTrainer

    chips = len(run.devices)
    built = training.build(run)
    mesh = make_mesh(n_devices=chips)
    trainer = SpmdTrainer(built["main"], built["startup"],
                          feed_names=built["feed_names"],
                          fetch_names=[built["fetch"].name], mesh=mesh)
    with run.clock.phase("startup"):
        trainer.init()
    training.to_master_type(run, list(trainer.state),
                            trainer.state.get, trainer.state.__setitem__)
    on_mesh = set(mesh.devices.flat)
    for name, value in trainer.state.items():
        if value.sharding.device_set != on_mesh:
            raise RuntimeError("state %s is on %s, not on the mesh"
                               % (name, value.devices()))

    sharding = NamedSharding(mesh, batch_spec((1,), mesh))
    pool = training.make_pool(run, built, sharding)
    for name, value in pool[0].items():
        if value.sharding.device_set != on_mesh:
            raise RuntimeError("feed %s is on %d device(s), not %d"
                               % (name, len(value.sharding.device_set),
                                  chips))

    first = run.devices[0]
    whole = jax.device_put(pool[0], first)
    want = training.reference_loss(
        run, built, lambda n: jax.device_put(trainer.state[n], first),
        whole)
    del whole

    def step(feeds):
        return trainer.step(feeds)[0]

    def settle():
        jax.block_until_ready(trainer.state)

    training.run_windows(run, built, step, settle, pool, want)
