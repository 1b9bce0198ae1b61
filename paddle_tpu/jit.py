"""Functional program export: turn a Program into a pure jittable function.

This is the TPU-native counterpart of handing a compiled inference/training
graph to callers (reference: paddle/inference/inference.h:23 InferenceEngine
runs a loaded ProgramDesc; paddle/framework/executor.cc:79 interprets it).
Here the whole block becomes ONE pure function of (state, feeds, rng) so it
can be jax.jit-ed, pjit-ed over a Mesh, differentiated, or exported.

The function is closed over the program structure only — parameters and
other persistable state flow through the `state` dict argument, so the
caller owns placement/sharding of every buffer.
"""

import jax

from .fluid.executor import ExecContext, apply_op, RNG_STATE_NAME
from .obs import trace as obs_trace

__all__ = ["FunctionalProgram", "functionalize", "state_from_scope",
           "state_to_scope"]


class FunctionalProgram:
    """A Program block as a pure function.

    __call__(state, feeds, rng=None) -> (fetches, new_state)
      state:   dict name -> array for every persistable var the block reads
               (parameters, BN moving stats, optimizer accumulators)
      feeds:   dict feed name -> array
      fetches: list of arrays in fetch_names order
      new_state: dict with the same keys as `state` (updated persistables)
    """

    def __init__(self, program, feed_names, fetch_names, block_idx=0):
        with obs_trace.span("startup/functional_program",
                            cat=obs_trace.STARTUP) as made:
            self._analyze(program, feed_names, fetch_names, block_idx)
            made.set(ops=len(self.ops), state=len(self.state_in_names))

    def _analyze(self, program, feed_names, fetch_names, block_idx):
        self.program = program
        self.feed_names = list(feed_names)
        self.fetch_names = list(fetch_names)
        self.block_idx = block_idx

        block_desc = program.desc.block(block_idx)
        self.ops = list(block_desc.ops)

        # persistable vars: anything marked persistable in any block var
        # table reachable from this block
        persist = set()
        bd = block_desc
        prog_desc = program.desc
        while True:
            for name, vd in bd.vars.items():
                if vd.persistable:
                    persist.add(name)
            if bd.parent_idx < 0:
                break
            bd = prog_desc.block(bd.parent_idx)

        reads, writes = set(), set()
        produced = set(self.feed_names)
        for od in self.ops:
            for n in od.input_names():
                if n != "@EMPTY@" and n not in produced:
                    reads.add(n)
            for n in od.output_names():
                if n != "@EMPTY@":
                    produced.add(n)
                    writes.add(n)
        # state the function needs in: persistable reads; state out:
        # persistable writes (e.g. BN moving stats, optimizer updates)
        self.state_in_names = sorted(persist & reads)
        self.state_out_names = sorted(persist & writes)

    def __call__(self, state, feeds, rng=None):
        env = dict(state)
        env.update(feeds)
        # rng rides the state dict (RNG_STATE_NAME) so stochastic ops
        # (dropout, sampling) stay pure: the advanced key is returned
        # in new_state and feeds the next step
        if rng is None:
            rng = env.pop(RNG_STATE_NAME, None)
        ctx = ExecContext(None, self.program, self.block_idx, env, rng=rng)
        for od in self.ops:
            apply_op(ctx, od)
        new_state = dict(state)
        for n in self.state_out_names:
            if n in env:
                new_state[n] = env[n]
        # only round-trip the key when the caller put it in state —
        # explicit rng= callers (ParallelTrainer) keep the state
        # structure unchanged for their sharding specs
        if ctx.rng is not None and RNG_STATE_NAME in state:
            new_state[RNG_STATE_NAME] = ctx.rng
        fetches = [env[n] for n in self.fetch_names]
        return fetches, new_state


def functionalize(program, feed_names, fetch_names, block_idx=0):
    return FunctionalProgram(program, feed_names, fetch_names, block_idx)


def state_from_scope(fp, scope=None):
    """Collect the initial state dict for a FunctionalProgram from a Scope
    (after the startup program ran)."""
    from .core.scope import global_scope

    scope = scope or global_scope()
    state = {}
    for n in set(fp.state_in_names) | set(fp.state_out_names):
        v = scope.get(n)
        if v is not None:
            state[n] = v
    return state


def state_to_scope(state, scope=None):
    from .core.scope import global_scope

    scope = scope or global_scope()
    for n, v in state.items():
        if n != RNG_STATE_NAME:
            scope.set(n, v)
