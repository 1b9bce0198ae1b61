"""A training cell driven the way README's quickstart trains:
`fluid.Executor.run(main, feed, fetch_list=[loss])` in a loop (path A),
on one chip.  In a traced run the same program and state then go through
`jit.FunctionalProgram` under one `jax.jit` (path B), which gives the
first A/B of the two execution paths.
"""

import time

from benchmark import training

FUNCTIONAL_STEPS = 20


def run(run):
    import jax
    import paddle_tpu.fluid as fluid

    built = training.build(run)
    main, loss = built["main"], built["fetch"]
    scope = fluid.Scope()
    exe = fluid.Executor(fluid.TPUPlace(0))
    with run.clock.phase("startup"):
        exe.run(built["startup"], scope=scope)
    training.to_master_type(run, list(scope.local_var_names()),
                            scope.get, scope.set)
    pool = training.make_pool(run, built)
    want = training.reference_loss(run, built, scope.get, pool[0])

    def step(feeds):
        return exe.run(main, feed=feeds, fetch_list=[loss], scope=scope,
                       return_numpy=False)[0]

    def settle():
        jax.block_until_ready(
            [scope.get(n) for n in scope.local_var_names()])

    def functional_path(facts):
        facts["functional_step_ms"] = functional_step_ms(
            run, built, scope, pool)

    training.run_windows(run, built, step, settle, pool, want,
                         after=functional_path)


def functional_step_ms(run, built, scope, pool):
    """Milliseconds a step of the same program and state takes through
    FunctionalProgram under one jax.jit with all state donated.  The
    state is the scope's own arrays, taken over and not copied: the
    scope is dead afterwards, which is why this runs last."""
    import jax
    from paddle_tpu.fluid.executor import RNG_STATE_NAME
    from paddle_tpu.jit import FunctionalProgram, state_from_scope

    fp = FunctionalProgram(built["main"], built["feed_names"],
                           [built["fetch"].name])
    state = state_from_scope(fp, scope)
    state[RNG_STATE_NAME] = scope.get(RNG_STATE_NAME)
    step = jax.jit(lambda s, f: fp(s, f), donate_argnums=(0,))
    n = 0
    while True:
        before = run.compiles.compiles
        fetches, state = step(state, pool[n % len(pool)])
        jax.block_until_ready(state)
        n += 1
        if run.compiles.compiles == before:
            break
        if n > 8:
            raise RuntimeError("path B still compiles after %d steps" % n)
    start = time.perf_counter()
    for i in range(FUNCTIONAL_STEPS):
        with run.span("bench/functional"):
            fetches, state = step(state, pool[(n + i) % len(pool)])
    jax.block_until_ready((fetches, state))
    ms = (time.perf_counter() - start) / FUNCTIONAL_STEPS * 1e3
    print("path B: %.3f ms/step over %d steps" % (ms, FUNCTIONAL_STEPS),
          flush=True)
    return ms
