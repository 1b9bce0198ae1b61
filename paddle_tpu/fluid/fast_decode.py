"""Compiled generation over a single-step fluid Program.

Bridges the Program stack to the dense jitted decoders
(models/decode.py): a user expresses ONE decode step as an ordinary
inference Program — token in, logits out, recurrent state threaded
through named feed/fetch pairs — and `ProgramDecoder` runs the whole
generation loop as one XLA executable (lax.scan + top_k), trained
weights closed over from the scope.

This is the deploy-path answer to the reference's host-side generation
(RecurrentGradientMachine::beamSearch, beam_search_op.cc — both
per-step host bookkeeping): same program-building workflow, ~15× the
decode throughput before counting the per-step device↔host hops the
host path would add on TPU (docs/DESIGN_jit_beam_search.md).  The LoD
beam ops remain for program parity.

Usage:
    decoder = ProgramDecoder(step_prog, token_name="tok",
                             logits_name=logits.name,
                             state_pairs=[("h_in", h_out.name)])
    toks, lengths = decoder.greedy(bos=1, eos=0, max_len=32,
                                   init_state={"h_in": h0})
    seqs, scores = decoder.beam(beam_size=4, bos=1, eos=0, max_len=32,
                                init_state={"h_in": h0})
"""

import contextlib
import itertools
import time

import numpy as np

import jax
import jax.numpy as jnp

from ..jit import FunctionalProgram, state_from_scope
from ..models.decode import (PREFILL_BLOCK, block_diffusion_decode,
                             greedy_decode, beam_search_decode_dense,
                             prefill, sample_decode)
from ..obs import telemetry
from ..obs.trace import STARTUP, emit_span, span

__all__ = ["ProgramDecoder"]

# the `call` argument of the `decode/call` spans: a count per process
_CALLS = itertools.count(1)


class ProgramDecoder:
    """Compiled greedy/sample/beam generation from a single-step Program,
    and generation by diffusion over blocks (`diffuse`) from a step that
    takes a block.

    The step program's contract: it reads a token feed (int tensor
    [batch]), any number of state feeds ([batch, ...]), and fetches
    logits ([batch, vocab]) plus one new-state fetch per state feed
    (`state_pairs` lists (feed_name, fetch_var_name) in order).
    Parameters and other persistables come from `scope` (default: the
    global scope the program was trained in).

    A call need not start at position 0: `init_state` may hand in
    caches that already hold a session and the position it ends at (a
    decode-pool chip is handed its caches by a prefill pool), and the
    prompt and the generated tokens continue from there.  A state handed
    in as a `jax.Array` is used where it lies and is not written to (no
    call donates its state), so one session on the device serves call
    after call; a host array goes to the device anew at every call.  `max_positions`
    is checked against the prompt and `max_len` alone (`_check_extent`):
    the caller of such a call answers for pos + prompt + max_len - 1 <=
    max_positions.  It is the extent of the *positions*; what a state
    feed holds of them is its own declaration in the Program (a window
    layer's ring holds `window` slots whatever the extent), and a state
    handed in is held to that, feed by feed.

    A step that can take a block of positions says so in its Program:
    its token feed is declared [batch, -1], it advances its state by
    the T >= 1 consecutive tokens of every row it is fed, and its
    logits are those of the last (`models/transformer_program.py
    build_transformer_cached_step_program`, `models/window_moe_program.py
    build_window_moe_cached_step_program`).  The decoder then feeds
    [rows, 1] at every decode step and beam row, and prefills a prompt
    `models.decode.PREFILL_BLOCK` positions an application instead of
    one.  Nothing but the declaration decides it.  How many positions an
    application takes is the step's to say too: an op of the Program
    that carries a `prefill_block` attr was sized for so many
    (`mla_cached_attention`, whose absorbed queries are a hundred times
    a token's hidden state: `models/latent_moe_program.py`), the decoder
    prefills by the smallest its Program states, and a step that states
    none keeps `PREFILL_BLOCK`.  A step whose attention reads a chosen
    set of the slots is such a step too, since its ops choose and attend
    a set a position of the block (`mla_index_select`, and
    `mla_cached_attention` or `cached_attention` with `Selected`: the
    latent builder with an `indexer`, `models/sparse_kv_moe_program.py`);
    a step whose ops took a single query's set would declare [batch]
    and be prefilled a position an application.
    """

    def __init__(self, program, token_name, logits_name, state_pairs=(),
                 scope=None, max_positions=None):
        with span("startup/decoder_init", cat=STARTUP):
            self._init(program, token_name, logits_name, state_pairs,
                       scope, max_positions)

    def _init(self, program, token_name, logits_name, state_pairs, scope,
              max_positions):
        self.token_name = token_name
        self.state_pairs = list(state_pairs)
        # the step program's position extent (KV-cache length /
        # position-embedding table size): writes past it would CLAMP
        # inside the compiled scatter and silently corrupt generation,
        # so greedy/beam validate against it up front when it is given
        self.max_positions = max_positions
        feed_names = [token_name] + [f for f, _ in self.state_pairs]
        fetch_names = [logits_name] + [o for _, o in self.state_pairs]
        self._fp = FunctionalProgram(program, feed_names, fetch_names)
        # the Program's own declaration of its token feed: [batch], or
        # [batch, -1] for a step that takes a block of positions
        self._takes_block = len(
            program.global_block().var(token_name).shape) == 2
        # the positions an application of the step prefills, where its
        # ops say what they were sized for (else PREFILL_BLOCK)
        self._prefill_block = min(
            (op.attrs["prefill_block"]
             for op in program.global_block().desc.ops
             if "prefill_block" in op.attrs), default=PREFILL_BLOCK)
        # what the Program declares of each state feed past its rows: a
        # step's caches need not be of one extent (a window layer's ring
        # beside a full layer's whole extent), so each feed is held to
        # its own declaration, not to `max_positions`
        block = program.global_block()
        self._declared = {f: tuple(block.var(f).shape)
                          for f, _ in self.state_pairs if block.has_var(f)}
        # the scope's device arrays as they are: a round trip through
        # the host would hold every weight twice on the device until
        # the scope lets go of its own
        params = state_from_scope(self._fp, scope)
        from_host = [n for n, v in params.items()
                     if not isinstance(v, jax.Array)]
        with span("startup/state_place", cat=STARTUP,
                  arrays=len(from_host)) as placed:
            nbytes = 0
            for n in from_host:
                host = np.asarray(params[n])
                nbytes += host.nbytes
                params[n] = jnp.asarray(host)
            placed.set(bytes=nbytes)
        self._params = params
        missing = sorted(set(self._fp.state_in_names) - set(self._params))
        if missing:
            raise ValueError(
                "scope has no values for %s — run the startup program "
                "(and training) in this scope before building the "
                "decoder" % missing)
        # one compiled executable per decode config (weights are a
        # runtime argument, so a serving loop pays trace+compile once)
        self._compiled = {}

    def _step_fn(self, params):
        fp = self._fp
        token = self.token_name
        pairs = self.state_pairs
        takes_block = self._takes_block

        def step(state, tok):
            # the decoders choose one token a row; a block-taking step
            # reads it as a block of one
            feeds = {token: tok[:, None] if takes_block and tok.ndim == 1
                     else tok}
            feeds.update({f: state[f] for f, _ in pairs})
            (logits, *new_states), _ = fp(params, feeds)
            return logits, {f: ns for (f, _), ns in zip(pairs,
                                                        new_states)}

        return step

    def _prep(self, init_state, batch_size):
        state = dict(init_state or {})
        missing = [f for f, _ in self.state_pairs if f not in state]
        if missing:
            raise ValueError("init_state missing %s" % missing)
        known = {f for f, _ in self.state_pairs}
        extra = sorted(set(state) - known)
        if extra:
            raise ValueError(
                "init_state has keys %s that are not in state_pairs %s"
                % (extra, sorted(known)))
        # what the call was handed, by where it was: a `jax.Array` is
        # taken where it lies (a session a caller put on the device once
        # costs its later calls nothing: 5.7 GB of caches through the
        # host were 4.2-5.9 s of an 11 s call, PERF.md section 6, PR 58),
        # anything else goes to the device from the host
        handed = {"host": 0, "device": 0}
        for f, v in state.items():
            if isinstance(v, jax.Array):
                handed["device"] += v.nbytes
                continue
            v = np.asarray(v)
            handed["host"] += v.nbytes
            state[f] = jnp.asarray(v)
        for f, value in state.items():
            declared = self._declared.get(f, ())
            if len(declared) == value.ndim and any(
                    d > 0 and d != n for d, n in
                    zip(declared[1:], value.shape[1:])):
                raise ValueError(
                    "init_state[%r] is %s, the step program declares %s: "
                    "a cache is handed in at the extent its layer "
                    "declares (a window layer's ring, a full layer's "
                    "whole extent)" % (f, value.shape, declared))
        if batch_size is None:
            if not state:
                raise ValueError(
                    "batch_size is required when the step program has "
                    "no state feeds")
            batch_size = next(iter(state.values())).shape[0]
        return state, batch_size, handed

    def _jitted(self, key, builder):
        if key not in self._compiled:
            self._compiled[key] = jax.jit(builder())
        return self._compiled[key]

    def _check_extent(self, max_len, prompt_len=0):
        """The positions a call writes, counted from slot 0: the prompt
        and `max_len`.  A call that starts past position 0 (a session
        handed in: caches already filled and a `pos` inside `init_state`
        that says how far) is not seen here, where `init_state` is
        opaque: its caller answers for pos + prompt + max_len - 1 <=
        max_positions."""
        if self.max_positions is None:
            return
        need = prompt_len + max_len - 1 if prompt_len else max_len
        if need > self.max_positions:
            raise ValueError(
                "decoding %d positions (prompt %d + %d generated) "
                "exceeds the step program's extent %d — the compiled "
                "scatter would clamp and corrupt the cache.  (Counted "
                "from slot 0: a position the call starts from inside "
                "init_state, such as a session's pos, is not seen here; "
                "its caller answers for pos + prompt + max_len - 1 <= %d)"
                % (need, prompt_len, max_len, self.max_positions,
                   self.max_positions))

    def _norm_prompt(self, prompt, max_len):
        """Validate and convert the optional prompt once; returns a
        numpy array or None."""
        if prompt is None:
            self._check_extent(max_len)
            return None
        prompt = np.asarray(prompt)
        if prompt.ndim != 2 or prompt.shape[1] == 0:
            raise ValueError(
                "prompt must be [batch, P>=1] tokens, got shape %s"
                % (prompt.shape,))
        self._check_extent(max_len, prompt.shape[1])
        return prompt

    def _prefilled_run(self, params, state, prompt, decode_fn, eos,
                      max_len):
        """Shared prompt path: prefill, then decode_fn(step, state,
        first) -> (tokens, final state) for the remaining max_len-1
        tokens (skipped when max_len == 1 — the 'predict one
        continuation token' call).  Returns (tokens, lengths, state)."""
        step = self._step_fn(params)
        state, first = prefill(step, state, prompt, self._takes_block,
                               self._prefill_block)
        if max_len == 1:
            toks = first[:, None]
        else:
            toks, state = decode_fn(step, state, first)
            toks = jnp.concatenate([first[:, None], toks], axis=1)
        lengths = jnp.argmax(toks == eos, axis=1) + 1
        lengths = jnp.where(jnp.any(toks == eos, axis=1), lengths,
                            max_len)
        return toks, lengths, state

    def greedy(self, bos, eos, max_len, batch_size=None, init_state=None,
               prompt=None, return_state=()):
        """Returns (tokens [batch, max_len], lengths [batch]).

        `prompt` (int [batch, P]) warms the decode state through the
        step program first (one scan of the prompt's positions, or of
        blocks of them where the step takes a block — for a KV-cache
        step program this is the prefill); the first output token is
        then the prompt's continuation and `bos` is ignored.

        `return_state` names state feeds whose values after the last
        step come back as a third result, {feed name: array}: a state
        pair the step only writes (its feed unread) is how a caller
        sees an intermediate of the step that chose the last token."""
        return_state = tuple(return_state)
        want = bool(return_state)
        with _Call(self, max_len) as call:
            state, batch_size, prompt = call.prep(init_state, batch_size,
                                                  prompt)

            def decode(step, st, bos, n):
                # (tokens, lengths, the state after the last step where
                # one is asked for)
                out = greedy_decode(step, st, bos=bos, eos=eos, max_len=n,
                                    batch_size=batch_size, with_state=want)
                return out[0], out[1], out[2] if want else {}

            def kept(toks, lengths, last):
                return toks, lengths, {f: last[f] for f in return_state}

            if prompt is None:
                fn = call.program(
                    ("greedy", bos, eos, max_len, batch_size, return_state),
                    lambda: lambda params, s: kept(*decode(
                        self._step_fn(params), s, bos, max_len)))
                with call.dispatch():
                    out = fn(self._params, state)
            else:
                fn = call.program(
                    ("greedy-prefill", eos, max_len, batch_size,
                     prompt.shape[1], return_state),
                    lambda: lambda params, s, p: kept(*self._prefilled_run(
                        params, s, p,
                        lambda step, st, first: decode(step, st, first,
                                                       max_len - 1)[::2],
                        eos, max_len)))
                with call.dispatch():
                    out = fn(self._params, state, prompt)
            toks, lengths, last = call.fetch(out)
        return (toks, lengths, last) if return_state else (toks, lengths)

    def sample(self, bos, eos, max_len, batch_size=None, init_state=None,
               prompt=None, seed=0, temperature=1.0, top_k=0):
        """Ancestral sampling (temperature / top-k).  With `prompt`,
        prefills first and samples the continuation."""
        with _Call(self, max_len) as call:
            state, batch_size, prompt = call.prep(init_state, batch_size,
                                                  prompt)
            key = ("sample", eos, max_len, batch_size, temperature, top_k,
                   None if prompt is None else prompt.shape[1],
                   bos if prompt is None else None)
            if prompt is None:
                fn = call.program(key, lambda: lambda params, s, rng:
                                  sample_decode(
                                      self._step_fn(params), s, bos=bos,
                                      eos=eos, max_len=max_len,
                                      batch_size=batch_size, rng=rng,
                                      temperature=temperature, top_k=top_k))
                with call.dispatch():
                    out = fn(self._params, state, jax.random.PRNGKey(seed))
            else:
                fn = call.program(
                    key,
                    lambda: lambda params, s, p, rng: self._prefilled_run(
                        params, s, p,
                        lambda step, st, first: (sample_decode(
                            step, st, bos=first, eos=eos,
                            max_len=max_len - 1, batch_size=batch_size,
                            rng=rng, temperature=temperature,
                            top_k=top_k)[0], None),
                        eos, max_len)[:2])
                with call.dispatch():
                    out = fn(self._params, state, prompt,
                             jax.random.PRNGKey(seed))
            return call.fetch(out)

    def diffuse(self, prompt, max_len, block_length, denoising_steps,
                remasking, confidence_threshold, mask_id, temperature=0.0,
                top_k=0, init_state=None, return_state=(), eos=None,
                seed=0, hold=("pos",)):
        """Generation by diffusion over blocks
        (`models.decode.block_diffusion_decode`, read there for the
        loop): `max_len` tokens after `prompt` [batch, P], in blocks of
        `block_length` positions that each take up to `denoising_steps`
        denoising passes and a commit, `remasking` one of
        `models.decode.REMASKING`.  A block's commit rides on the next
        block's first denoising pass, one application of the step over
        both blocks; the last block's is an application of its own.
        Greedy at `temperature` 0, else sampled as `sample` samples
        (temperature, `top_k`, `seed`).

        The step Program takes a block (token feed [batch, -1]) under a
        block-causal mask of `block_length` (`cached_attention`'s
        `diffusion_block`) and fetches the logits of every position it
        is fed, [batch, T, vocab]
        (`models/diffusion_moe_program.py`); `hold` names the state
        feeds that count positions (the position: the step adds the
        positions it was fed), which the loop sets itself.  The extent
        has to hold the prompt's whole blocks and every generated block
        whole.

        Returns (tokens [batch, max_len], lengths [batch], info): info
        holds "denoise_passes" and "commit_passes" (ints: the denoising
        passes and the blocks committed, one a block wherever its commit
        ran, not the step's applications), "folded_commits" (of the
        commits, those that rode on a denoising pass: every block's but
        the last) and "step_applications" (the step's applications
        after the prefill: every denoising pass and the last commit),
        "fixed_pass" [batch, max_len] int32 and "fixed_conf" [batch,
        max_len] float32 (the pass of its block that fixed a position,
        and the confidence it was fixed at), and "state", {feed: array}
        of the `return_state` feeds after the last commit."""
        return_state = tuple(return_state)
        if not self._takes_block:
            raise ValueError(
                "diffuse: the step program's token feed %r is declared "
                "[batch]: a pass feeds a block of positions, so the step "
                "declares [batch, -1]" % self.token_name)
        with _Call(self, max_len) as call:
            state, batch_size, prompt = call.prep(init_state, None, prompt)
            if prompt is None:
                raise ValueError("diffuse: a prompt [batch, P >= 1]")

            def kept(*out):     # of the last state, what was asked for
                return out[:-1] + ({f: out[-1][f] for f in return_state},)

            blocks = -(-(prompt.shape[1] + max_len) // block_length)
            if self.max_positions is not None \
                    and blocks * block_length > self.max_positions:
                raise ValueError(
                    "diffuse: %d blocks of %d positions (prompt %d + %d "
                    "generated) exceed the step program's extent %d"
                    % (blocks, block_length, prompt.shape[1], max_len,
                       self.max_positions))
            fn = call.program(
                ("diffuse", max_len, batch_size, prompt.shape[1],
                 block_length, denoising_steps, remasking,
                 confidence_threshold, mask_id, temperature, top_k, eos,
                 return_state, tuple(hold)),
                lambda: lambda params, s, p, rng: kept(*block_diffusion_decode(
                    self._step_fn(params), s, p, max_len, block_length,
                    denoising_steps, mask_id, remasking,
                    confidence_threshold, temperature, top_k, rng, eos,
                    tuple(hold), self._prefill_block)))
            call.span.set(block_length=block_length,
                          denoising_steps=denoising_steps)
            with call.dispatch():
                out = fn(self._params, state, prompt,
                         jax.random.PRNGKey(seed))
            toks, lengths, passes, at, conf, last = call.fetch(out)
            counted = {"denoise_passes": int(passes["denoise"]),
                       "commit_passes": int(passes["commit"]),
                       "folded_commits": int(passes["folded"]),
                       "step_applications": int(passes["applications"])}
            call.span.set(**counted)
            telemetry.on_diffusion_call(tokens=toks.size, **counted)
        return toks, lengths, dict(counted, fixed_pass=at, fixed_conf=conf,
                                   state=last)

    def beam(self, beam_size, bos, eos, max_len, batch_size=None,
             init_state=None, length_penalty=0.0):
        """Returns (sequences [batch, beam, max_len], scores
        [batch, beam]), best first."""
        with _Call(self, max_len, beam_size) as call:
            state, batch_size, _ = call.prep(init_state, batch_size, None)
            fn = call.program(
                ("beam", beam_size, bos, eos, max_len, batch_size,
                 length_penalty),
                lambda: lambda params, s: beam_search_decode_dense(
                    self._step_fn(params), s, bos=bos, eos=eos,
                    beam_size=beam_size, max_len=max_len,
                    batch_size=batch_size, length_penalty=length_penalty))
            with call.dispatch():
                out = fn(self._params, state)
            return call.fetch(out)


class _Call:
    """One public call of a `ProgramDecoder`, under its spans and
    counters: `decode/call` holds `decode/prep` (`prep`: validation, the
    state's and the prompt's way to the device), `decode/dispatch`
    (`dispatch`, around the jitted call until it returns: trace, lower
    and compile or cache load on a new key, the enqueue otherwise) and
    `decode/fetch` (`fetch`: the wait for the device and the results'
    way to the host).  Nothing here waits beyond what the call did
    before it had spans: a transfer still in flight when `prep` returns
    is waited for by the device, under whichever span is open then.  The
    same three intervals feed `decoder_seconds_total` when the call ends
    without an error, with no profiler session too.  The mode builds its
    function and calls it in its own frame, between these."""

    def __init__(self, decoder, max_len, beam_size=1):
        self.decoder, self.max_len, self.beam_size = (decoder, max_len,
                                                      beam_size)
        self.seconds = {}

    def __enter__(self):
        self.span = span("decode/call", cat="decoder", call=next(_CALLS),
                         max_len=self.max_len)
        self.span.__enter__()
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, exc_type, exc, tb):
        self.span.__exit__(exc_type, exc, tb)
        if exc_type is None:
            if self.built:
                # a call that built its program is a part of start-up
                # (the jit phases are inside it); one on a key the
                # decoder has leaves nothing
                emit_span("startup/decoder_build", self.t0,
                          time.perf_counter() - self.t0, cat=STARTUP,
                          args={"mode": self.mode, "batch": self.batch_size,
                                "prompt_len": self.prompt_len,
                                "max_len": self.max_len})
            telemetry.on_decoder_call(
                self.mode, self.built, self.batch_size * self.beam_size,
                self.prompt_len, self.max_len, self.handed["host"],
                self.handed["device"],
                [self.seconds[p] for p in ("prep", "dispatch", "fetch")])
        return False

    @contextlib.contextmanager
    def _phase(self, name):
        """`decode/<name>`, and its seconds."""
        t0 = time.perf_counter()
        with span("decode/" + name, cat="decoder") as phase:
            yield phase
        self.seconds[name] = time.perf_counter() - t0

    def prep(self, init_state, batch_size, prompt):
        """(the state on the device, the batch size, the prompt on the
        device or None)."""
        decoder = self.decoder
        with self._phase("prep") as prep:
            state, self.batch_size, self.handed = decoder._prep(
                init_state, batch_size)
            prompt = decoder._norm_prompt(prompt, self.max_len)
            self.prompt_len = 0 if prompt is None else prompt.shape[1]
            if prompt is not None:
                prompt = jnp.asarray(prompt)
            prep.set(host_bytes=self.handed["host"],
                     device_bytes=self.handed["device"])
        return state, self.batch_size, prompt

    def program(self, key, builder):
        """The jitted function of `key` (its first entry the mode)."""
        decoder = self.decoder
        self.mode, self.built = key[0], key not in decoder._compiled
        self.span.set(mode=self.mode, batch=self.batch_size,
                      prompt_len=self.prompt_len,
                      block=decoder._prefill_block
                      if decoder._takes_block else 1,
                      built=int(self.built))
        return decoder._jitted(key, builder)

    def dispatch(self):
        return self._phase("dispatch")

    def fetch(self, out):
        with self._phase("fetch"):
            return jax.tree_util.tree_map(np.asarray, out)
