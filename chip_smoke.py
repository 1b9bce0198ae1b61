"""The quickest proof that the system still starts on the chip.

    python chip_smoke.py

drives the main path once on a TPU, through the entry points a user
calls and at the widths the repo already uses, and checks what comes
out: ResNet-50 trained through `Executor.run` and through one jitted
FunctionalProgram step with donated state, the Program-stack transformer
trained through the flash-attention kernel (and the kernel checked
against dense attention), the routed expert op forward and backward at
OLMoE's widths against the dense reference, the state-space scan op
forward and backward at granite-4.0-h-micro's widths against the
sequential recurrence, the Mamba-1 scan op with its carried state at
Phi-4-mini-flash-reasoning's widths (a block against its steps and
against the recurrence), ResNet-50 served over HTTP
as serve_cli
serves it, and — on a host with four chips — ResNet-50 under
SpmdTrainer.  Weights are random, from a seed; no phase is cut down.

One process holds the chip from start to end and starts no other.  It
needs no network, no native runtime and no file outside the checkout
but the compile cache when JAX_COMPILATION_CACHE_DIR places it
elsewhere.

Exit code 0 and, as the last line of stdout,
{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}
only when every phase passed on a TPU.  Anything else — no accelerator,
a failed check, an exception — ends the run non-zero with no such line.
"""

import http.client
import json
import os
import re
import shutil
import sys
import time

import numpy as np

CHECKOUT = os.path.dirname(os.path.abspath(__file__))
# bf16 keeps 8 bits of mantissa: errors are judged against the largest
# magnitude of the reference tensor
BF16_TOL = 2e-2


def check(ok, message):
    if not ok:
        raise RuntimeError("chip_smoke: " + message)


def check_falling(name, losses):
    print("  %s losses: %s" % (name, " ".join("%.4f" % v for v in losses)),
          flush=True)
    check(all(np.isfinite(losses)), "%s: non-finite loss" % name)
    check(losses[-1] < losses[0],
          "%s: loss did not fall (%r)" % (name, losses))


def check_on(name, arrays, devices):
    """Every jax array of `arrays` lives on `devices` and nowhere else."""
    import jax

    arrays = [a for a in arrays if isinstance(a, jax.Array)]
    check(arrays, "%s: no device arrays to look at" % name)
    for a in arrays:
        check(a.devices() <= devices,
              "%s: an array sits on %s, outside %s"
              % (name, a.devices(), devices))


class CompileClock:
    """What JAX compiled since the last `lap()`: seconds inside the
    backend compile call (a persistent-cache hit counts its load time
    there) and persistent-cache hits and misses."""

    def __init__(self):
        import jax.monitoring

        self._seconds = self._hits = self._misses = 0
        jax.monitoring.register_event_duration_secs_listener(
            self._on_duration)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_duration(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self._seconds += duration

    def _on_event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self._hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self._misses += 1

    def lap(self):
        out = (self._seconds, self._hits, self._misses)
        self._seconds = self._hits = self._misses = 0
        return out


def scalar(fetch):
    return float(np.asarray(fetch).reshape(-1)[0])


def build_image_model(model, batch, image_size, class_dim):
    """(main, startup, logits, loss): `paddle_tpu.models.<model>` with
    its loss and a momentum optimizer, as `__graft_entry__` builds it."""
    from __graft_entry__ import _build_model
    from paddle_tpu import models
    from paddle_tpu.models.image_train import MODELS

    return _build_model(getattr(models, model), batch, image_size,
                        class_dim, with_loss=True,
                        channels=MODELS[model]["channels"])


def image_feeds(batch, image_size, class_dim, channels=3):
    rs = np.random.RandomState(0)
    image = rs.rand(batch, channels, image_size,
                    image_size).astype(np.float32)
    label = rs.randint(0, class_dim, size=(batch, 1)).astype(np.int64)
    return {"image": image, "label": label}


def functional_step(main_prog, feed_names, fetch_name, scope, dev):
    """(step, state): the whole program through FunctionalProgram under
    one jax.jit, every state array on `dev` and donated to the call."""
    import jax
    from paddle_tpu.analysis.alias import state_donation
    from paddle_tpu.fluid.executor import RNG_STATE_NAME
    from paddle_tpu.jit import FunctionalProgram, state_from_scope

    fp = FunctionalProgram(main_prog, feed_names, [fetch_name])
    state = {n: jax.device_put(np.asarray(v), dev)
             for n, v in state_from_scope(fp, scope).items()}
    # stochastic ops (dropout) draw from a state-carried key
    state[RNG_STATE_NAME] = jax.device_put(jax.random.PRNGKey(0), dev)
    step = jax.jit(lambda s, f: fp(s, f),
                   donate_argnums=(0,) if state_donation() else ())
    return step, state


# ---------------------------------------------------------------------------
# phases
# ---------------------------------------------------------------------------

def resnet50_train(batch=128, image_size=224, class_dim=1000):
    import jax
    import paddle_tpu.fluid as fluid

    devices = set(jax.devices())
    fluid.amp.enable_bf16()
    main, startup, _, loss = build_image_model(
        "resnet50", batch, image_size, class_dim)
    feeds = image_feeds(batch, image_size, class_dim)

    scope = fluid.Scope()
    exe = fluid.Executor(fluid.TPUPlace(0))
    exe.run(startup, scope=scope)
    losses = [scalar(exe.run(main, feed=feeds, fetch_list=[loss],
                             scope=scope)[0]) for _ in range(3)]
    check_falling("Executor.run", losses)
    check_on("executor scope",
             [scope.get(n) for n in scope.local_var_names()], devices)

    step, state = functional_step(
        main, ["image", "label"], loss.name, scope, jax.devices()[0])
    dev_feeds = jax.device_put(feeds, jax.devices()[0])
    losses = []
    for _ in range(5):
        (fetch,), state = step(state, dev_feeds)
        losses.append(scalar(fetch))
    check_falling("FunctionalProgram step", losses)
    check_on("functional state", state.values(), devices)


def flash_kernel_check(shape, causal, num_heads=None):
    """flash_attention against reference_attention on one input:
    outputs and all three gradients.  `shape` is
    [batch, heads, seq, dim], or with `num_heads`
    [batch, seq, num_heads * dim], where the kernels pick the heads
    themselves."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.kernels.flash_attention import (
        flash_attention, flash_attention_with_lse, merge_heads,
        reference_attention, split_heads)

    flash, dense = flash_attention, reference_attention
    if num_heads is not None:
        def flash(q, k, v, sm_scale, causal):
            return flash_attention_with_lse(q, k, v, sm_scale, causal,
                                            num_heads=num_heads)[0]

        def dense(q, k, v, sm_scale, causal):
            return merge_heads(reference_attention(
                *(split_heads(x, num_heads) for x in (q, k, v)), sm_scale,
                causal))

    q, k, v, do = (
        jax.random.normal(key, shape, jnp.float32).astype(jnp.bfloat16)
        for key in jax.random.split(jax.random.PRNGKey(0), 4))

    def run(attention):
        def loss(q, k, v):
            o = attention(q, k, v, None, causal)
            return jnp.sum(o.astype(jnp.float32)
                           * do.astype(jnp.float32)), o

        (_, o), grads = jax.jit(jax.value_and_grad(
            loss, argnums=(0, 1, 2), has_aux=True))(q, k, v)
        return (o,) + grads

    worst = 0.0
    for name, got, want in zip(("o", "dq", "dk", "dv"),
                               run(flash), run(dense)):
        got = np.asarray(got, np.float32)
        want = np.asarray(want, np.float32)
        check(got.shape == want.shape and np.isfinite(got).all(),
              "flash %s %s causal=%s: bad shape or non-finite"
              % (name, shape, causal))
        err = float(np.abs(got - want).max() / np.abs(want).max())
        check(err < BF16_TOL,
              "flash %s %s causal=%s: off the reference by %.4f of its "
              "largest value" % (name, shape, causal, err))
        worst = max(worst, err)
    print("  flash_attention %s%s causal=%s: within %.4f of the reference"
          % (shape, "" if num_heads is None else " of %d heads" % num_heads,
             causal, worst), flush=True)


def transformer_train(batch=16, seq_len=512, d_model=512, n_layer=6,
                      n_head=8, vocab=8192,
                      kernel_shapes=((2, 8, 512, 64), (1, 8, 4096, 128))):
    import jax
    import paddle_tpu.fluid as fluid
    from paddle_tpu.models.transformer_program import (
        build_transformer_program, transformer_program_feeds)

    fluid.amp.enable_bf16()
    main, startup, loss, _ = build_transformer_program(
        batch, seq_len, vocab, n_layer=n_layer, n_head=n_head,
        d_model=d_model)
    with fluid.program_guard(main, startup):
        fluid.optimizer.MomentumOptimizer(
            learning_rate=0.01, momentum=0.9).minimize(loss)

    scope = fluid.Scope()
    fluid.Executor(fluid.TPUPlace(0)).run(startup, scope=scope)
    dev = jax.devices()[0]
    step, state = functional_step(
        main, ["tokens", "positions", "targets"], loss.name, scope, dev)
    feeds = jax.device_put(
        transformer_program_feeds(batch, seq_len, vocab), dev)
    # the Mosaic custom call in the step is the proof that neither the
    # pallas interpreter nor reference_attention stood in for the kernel
    check("tpu_custom_call" in step.lower(state, feeds).as_text(),
          "transformer step lowered without a Mosaic kernel")
    losses = []
    for _ in range(3):
        (fetch,), state = step(state, feeds)
        losses.append(scalar(fetch))
    check_falling("transformer step", losses)
    check_on("transformer state", state.values(), set(jax.devices()))

    for shape in kernel_shapes:
        for causal in (False, True):
            flash_kernel_check(shape, causal)
    # heads of 64 side by side as a projection writes them, two a grid
    # step: what the `flash_attention` op hands the kernels
    flash_kernel_check((2, 1024, 512), True, num_heads=8)


def moe_experts_check(tokens=4096, hidden=2048, experts=64, width=1024,
                      top_k=8):
    """The routed expert op (`moe_experts`: ordering, the grouped Pallas
    kernels, the combine) and its explicit gradient at OLMoE's widths,
    bfloat16 products, against every expert applied densely to every
    token in float32 and weighted afterwards (as the plain reference,
    models/reference/olmoe.py, applies them): the output, and the
    gradients of the input, the routing weights and the three stacks of
    matrices."""
    import jax
    import jax.numpy as jnp
    import paddle_tpu.fluid as fluid
    from paddle_tpu.ops import registry

    fluid.amp.enable_bf16()
    keys = jax.random.split(jax.random.PRNGKey(0), 6)
    x = jax.random.normal(keys[0], (tokens, hidden), jnp.float32) \
        .astype(jnp.bfloat16)
    d_out = jax.random.normal(keys[1], (tokens, hidden), jnp.float32) \
        .astype(jnp.bfloat16)
    w_gate, w_up = (jax.random.normal(k, (experts, hidden, width),
                                      jnp.float32) * hidden ** -0.5
                    for k in keys[2:4])
    w_down = jax.random.normal(keys[4], (experts, width, hidden),
                               jnp.float32) * width ** -0.5
    probs = jax.nn.softmax(
        jax.random.normal(keys[5], (tokens, experts), jnp.float32))
    top_w, top_idx = jax.lax.top_k(probs, top_k)
    info = registry.get_op_info("moe_experts")

    def program(x, top_w, w_gate, w_up, w_down):
        ins = {"X": [x], "TopW": [top_w], "TopIdx": [top_idx],
               "WGate": [w_gate], "WUp": [w_up], "WDown": [w_down]}
        outs = info.kernel(None, ins, {})
        grad_ins = dict(ins, **{"OG@Out": [d_out]})
        grad_ins.update({"O@" + slot: v for slot, v in outs.items()})
        grads = info.grad_kernel(None, grad_ins, {})
        return [outs["Out"][0]] + [grads[s + "@GRAD"][0] for s in (
            "X", "TopW", "WGate", "WUp", "WDown")], outs["Counts"][0]

    def plain(x, top_w, w_gate, w_up, w_down):
        def out(x, top_w, w_gate, w_up, w_down):
            weights = jnp.zeros((tokens, experts)).at[
                jnp.arange(tokens)[:, None], top_idx].set(top_w)

            def add_expert(m, expert):
                gate, up, down, weight = expert
                h = jax.nn.silu(x @ gate) * (x @ up)
                return m + weight[:, None] * (h @ down), None

            # a scan: 64 experts unrolled take minutes to compile
            return jax.lax.scan(add_expert, jnp.zeros_like(x), (
                w_gate, w_up, w_down, weights.T))[0]

        with jax.default_matmul_precision("highest"):
            m, vjp = jax.vjp(out, x, top_w, w_gate, w_up, w_down)
            return [m] + list(vjp(d_out.astype(jnp.float32)))

    got, counts = jax.jit(program)(x, top_w, w_gate, w_up, w_down)
    check("tpu_custom_call" in jax.jit(program).lower(
        x, top_w, w_gate, w_up, w_down).as_text(),
        "moe_experts lowered without a Mosaic kernel")
    want = jax.jit(plain)(x.astype(jnp.float32), top_w, w_gate, w_up, w_down)
    counts = np.asarray(counts)
    check(int(counts.sum()) == tokens * top_k,
          "moe_experts: %d rows for %d assignments"
          % (counts.sum(), tokens * top_k))
    worst = 0.0
    for name, g, w in zip(("out", "dx", "dtop_w", "dw_gate", "dw_up",
                           "dw_down"), got, want):
        g, w = np.asarray(g, np.float32), np.asarray(w, np.float32)
        check(g.shape == w.shape and np.isfinite(g).all(),
              "moe_experts %s: bad shape or non-finite" % name)
        err = float(np.abs(g - w).max() / np.abs(w).max())
        check(err < BF16_TOL, "moe_experts %s: off the reference by %.4f "
              "of its largest value" % (name, err))
        worst = max(worst, err)
    print("  moe_experts [%d, %d] x %d experts of %d, top-%d (rows an "
          "expert %d..%d): within %.4f of the reference"
          % (tokens, hidden, experts, width, top_k, counts.min(),
             counts.max(), worst), flush=True)


def ssd_scan_check(seq=4096, heads=64, dim=64, state=128, chunk=256):
    """The state-space scan op (`ssd_scan`: the chunked Mosaic kernels,
    the carried state over 16 chunks) and its explicit gradient at
    granite-4.0-h-micro's widths, bfloat16 products, against the
    recurrence walked one position after another in float32 (as the
    plain reference, models/reference/granite_hybrid.py, walks it): the
    output and the seven gradients."""
    import jax
    import jax.numpy as jnp
    import paddle_tpu.fluid as fluid
    from paddle_tpu.models.reference import granite_hybrid as reference
    from paddle_tpu.ops import registry

    fluid.amp.enable_bf16()
    keys = jax.random.split(jax.random.PRNGKey(0), 8)
    wide, f32 = (1, seq, heads * dim), jnp.float32
    slots = ("X", "Dt", "DtBias", "ALog", "B", "C", "D")
    values = (
        jax.random.normal(keys[0], wide, f32),
        jax.random.normal(keys[1], (1, seq, heads), f32),
        # steps log-uniform on [1e-3, 1e-1], rates uniform on [1, 16]:
        # decays of 0.2 to 0.999 a step, state that crosses chunks
        jnp.log(jnp.expm1(jnp.exp(jax.random.uniform(
            keys[2], (heads,), f32, np.log(1e-3), np.log(1e-1))))),
        jnp.log(jax.random.uniform(keys[3], (heads,), f32, 1.0, 16.0)),
        0.5 * jax.random.normal(keys[4], (1, seq, state), f32),
        0.5 * jax.random.normal(keys[5], (1, seq, state), f32),
        1.0 + 0.1 * jax.random.normal(keys[6], (heads,), f32))
    d_y = jax.random.normal(keys[7], wide, f32)
    info = registry.get_op_info("ssd_scan")
    attrs = {"num_heads": heads, "chunk_size": chunk}

    def program(*values):
        ins = {s: [v] for s, v in zip(slots, values)}
        outs = info.kernel(None, ins, attrs)
        grad_ins = dict(ins, **{"OG@Y": [d_y.astype(outs["Y"][0].dtype)]})
        grad_ins.update({"O@" + slot: v for slot, v in outs.items()})
        grads = info.grad_kernel(None, grad_ins, attrs)
        return [outs["Y"][0]] + [grads[s + "@GRAD"][0] for s in slots]

    def plain(x, dt, dt_bias, a_log, b, c, d_skip):
        def out(*v):
            x, dt, dt_bias, a_log, b, c, d_skip = v
            return reference.recurrence(
                x.reshape(1, seq, heads, dim),
                jax.nn.softplus(dt + dt_bias), -jnp.exp(a_log), b, c,
                d_skip, segment=64).reshape(wide)

        with jax.default_matmul_precision("highest"):
            y, vjp = jax.vjp(out, x, dt, dt_bias, a_log, b, c, d_skip)
            return [y] + list(vjp(d_y))

    got = jax.jit(program)(*values)
    check(jax.jit(program).lower(*values).as_text().count(
        "tpu_custom_call") == 2,
        "ssd_scan and its gradient lowered without their two Mosaic "
        "kernels")
    want = jax.jit(plain)(*values)
    worst = 0.0
    for name, g, w in zip(("y",) + tuple("d" + s for s in slots), got,
                          want):
        g, w = np.asarray(g, np.float32), np.asarray(w, np.float32)
        check(g.shape == w.shape and np.isfinite(g).all(),
              "ssd_scan %s: bad shape or non-finite" % name)
        err = float(np.abs(g - w).max() / np.abs(w).max())
        check(err < BF16_TOL, "ssd_scan %s: off the recurrence by %.4f of "
              "its largest value" % (name, err))
        worst = max(worst, err)
    print("  ssd_scan [1, %d, %d x %d], state %d, %d chunks of %d: within "
          "%.4f of the recurrence" % (seq, heads, dim, state, seq // chunk,
                                      chunk, worst), flush=True)
    ssd_scan_carried_check()


def ssd_scan_carried_check(rows=4, heads=128, dim=64, state=128, chunk=256,
                           steps=8):
    """The same op with its state handed in and on (`State` /
    `StateOut`: kernels/ssd.py's `ssd_block_*`, kernels/ssd_step.py's
    `ssd_step_*`) at
    granite-4.0-h-small's widths: a prompt of two chunks as one block
    from zeros, then `steps` positions a step at a time, the state
    through both borders, against the recurrence walked one position
    after another in float32 over the whole sequence
    (models/reference/granite_moe_hybrid.py): the output at every
    position and the state after the last."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.models.reference import granite_moe_hybrid as reference
    from paddle_tpu.ops import registry, ssm

    seq = 2 * chunk + steps
    keys = jax.random.split(jax.random.PRNGKey(1), 7)
    wide, f32 = (rows, seq, heads * dim), jnp.float32
    slots = ("X", "Dt", "DtBias", "ALog", "B", "C", "D")
    values = (
        jax.random.normal(keys[0], wide, f32),
        jax.random.normal(keys[1], (rows, seq, heads), f32),
        jnp.log(jnp.expm1(jnp.exp(jax.random.uniform(
            keys[2], (heads,), f32, np.log(1e-3), np.log(1e-1))))),
        jnp.log(jax.random.uniform(keys[3], (heads,), f32, 1.0, 16.0)),
        0.5 * jax.random.normal(keys[4], (rows, seq, state), f32),
        0.5 * jax.random.normal(keys[5], (rows, seq, state), f32),
        1.0 + 0.1 * jax.random.normal(keys[6], (heads,), f32))
    info = registry.get_op_info("ssd_scan")
    attrs = {"num_heads": heads, "chunk_size": chunk}

    def part(values, start, stop, carried):
        ins = {s: [v[:, start:stop] if v.ndim == 3 else v]
               for s, v in zip(slots, values)}
        outs = info.kernel(None, dict(ins, State=[carried]), attrs)
        return outs["Y"][0], outs["StateOut"][0]

    def program(*values):
        carried = jnp.zeros((rows, state, heads * dim), f32)
        ys = []
        for start, stop in [(0, 2 * chunk)] + [
                (at, at + 1) for at in range(2 * chunk, seq)]:
            y, carried = part(values, start, stop, carried)
            ys.append(y)
        return jnp.concatenate(ys, axis=1), carried

    def plain(x, dt, dt_bias, a_log, b, c, d_skip):
        with jax.default_matmul_precision("highest"):
            y, last = reference.recurrence(
                {}, x.reshape(rows, seq, heads, dim),
                jax.nn.softplus(dt + dt_bias), -jnp.exp(a_log), b, c,
                d_skip)
        return y.reshape(wide), last

    text = jax.jit(program).lower(*values).as_text()
    check(text.count("ssd_block_c%d" % chunk) >= 1,
          "ssd_scan with a state lowered without its block kernel")
    check(text.count("ssd_step_r%d_b" % rows) >= 1,
          "ssd_scan with a state lowered a step without its step kernel")
    y, last = jax.jit(program)(*values)
    want_y, want_last = jax.jit(plain)(*values)
    worst = 0.0
    for name, g, w in (("y", y, want_y),
                       ("state", ssm.heads_apart(last, heads), want_last)):
        g, w = np.asarray(g, np.float32), np.asarray(w, np.float32)
        check(g.shape == w.shape and np.isfinite(g).all(),
              "ssd_scan with a state, %s: bad shape or non-finite" % name)
        err = float(np.abs(g - w).max() / np.abs(w).max())
        check(err < BF16_TOL, "ssd_scan with a state, %s: off the "
              "recurrence by %.4f of its largest value" % (name, err))
        worst = max(worst, err)
    print("  ssd_scan with its state [%d, %d + %d x 1, %d x %d], state %d: "
          "a block of two chunks, then %d steps, within %.4f of the "
          "recurrence" % (rows, 2 * chunk, steps, heads, dim, state, steps,
                          worst), flush=True)


def selective_scan_check(rows=16, block=128, channels=5120, state=16):
    """The Mamba-1 scan op with its carried state (`selective_scan`) at
    Phi-4-mini-flash-reasoning's widths: a block of 128 positions from an
    entering state against 128 single steps (the output and the state
    handed on), and the first row against the recurrence walked in
    float64 on the host."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.ops import registry

    keys = jax.random.split(jax.random.PRNGKey(0), 7)
    f32 = jnp.float32
    wide = (rows, block, channels)
    ins = {
        "X": [jax.random.normal(keys[0], wide, f32).astype(jnp.bfloat16)],
        "Dt": [0.3 * jax.random.normal(keys[1], wide, f32)],
        # steps log-uniform on [1e-3, 1e-1], rates 1 .. state
        "DtBias": [jnp.log(jnp.expm1(jnp.exp(jax.random.uniform(
            keys[2], (channels,), f32, np.log(1e-3), np.log(1e-1)))))],
        "ALog": [jnp.broadcast_to(jnp.log(jnp.arange(1, state + 1,
                                                     dtype=f32)),
                                  (channels, state))],
        "B": [jax.random.normal(keys[3], (rows, block, state), f32)],
        "C": [jax.random.normal(keys[4], (rows, block, state), f32)],
        "D": [jnp.ones((channels,), f32)],
        "State": [jax.random.normal(keys[5], (rows, state, channels), f32)]}
    kernel = registry.get_op_info("selective_scan").kernel
    moving = ("X", "Dt", "B", "C")

    def whole(ins):
        out = kernel(None, ins, {})
        return out["Out"][0], out["StateOut"][0]

    def stepped(ins):
        def one(state, at):
            out = kernel(None, dict(
                ins, State=[state],
                **{k: [v[:, None]] for k, v in zip(moving, at)}), {})
            return out["StateOut"][0], out["Out"][0][:, 0]

        state, ys = jax.lax.scan(one, ins["State"][0], tuple(
            jnp.moveaxis(ins[k][0], 1, 0) for k in moving))
        return jnp.moveaxis(ys, 0, 1), state

    got_y, got_s = jax.jit(whole)(ins)
    want_y, want_s = jax.jit(stepped)(ins)
    for name, g, w in (("Out", got_y, want_y), ("StateOut", got_s, want_s)):
        g, w = np.asarray(g, np.float32), np.asarray(w, np.float32)
        check(np.isfinite(g).all() and np.abs(g - w).max()
              <= 1e-5 * np.abs(w).max(),
              "selective_scan %s: a block is not its steps" % name)
    x, dt, b, c = (np.asarray(ins[k][0][0], np.float64) for k in moving)
    dt = np.log1p(np.exp(dt + np.asarray(ins["DtBias"][0], np.float64)))
    a = -np.exp(np.asarray(ins["ALog"][0], np.float64)).T
    s = np.asarray(ins["State"][0][0], np.float64)
    ys = []
    for t in range(block):
        s = np.exp(dt[t] * a) * s + (dt[t] * x[t]) * b[t][:, None]
        ys.append((s * c[t][:, None]).sum(0) + x[t])
    err = float(np.abs(np.asarray(got_y[0], np.float64) - np.stack(ys)).max()
                / np.abs(np.stack(ys)).max())
    check(err < BF16_TOL, "selective_scan: off the recurrence by %.4f of "
          "its largest value" % err)
    print("  selective_scan [%d, %d, %d], state %d: a block is its steps, "
          "within %.4f of the recurrence" % (rows, block, channels, state,
                                             err), flush=True)


def block_causal_walk_check(rows=8, heads=32, kv_heads=4, dim=128, slots=1024,
                            diffusion_block=4):
    """`cached_attention` under the block-causal mask of generation by
    diffusion over blocks (`diffusion_block`: the walk of the live slots,
    `gqa_decode_k<slots>_t<T>_b<B>`) at SDAR-30B-A3B-Chat's widths, a
    pass over one block (T = 4) behind 640 stored slots, a commit and
    the next block's first pass in one application (T = 8) from 644, a
    multiple of 4 and not of 8, and a prefill block (T = 128) behind
    128, against a float32 softmax over the slots to the end of each
    query's block, on the device."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.ops import registry

    kernel = registry.get_op_info("cached_attention").kernel
    bf16, group = jnp.bfloat16, heads // kv_heads
    for positions, pos in ((diffusion_block, 640),
                           (2 * diffusion_block, 644), (128, 128)):
        keys = jax.random.split(jax.random.PRNGKey(positions), 5)
        q = jax.random.normal(keys[0], (rows, positions, heads * dim), bf16)
        new = [jax.random.normal(k, (rows, positions, kv_heads * dim), bf16)
               for k in keys[1:3]]
        caches = [jax.random.normal(k, (rows, kv_heads, slots, dim), bf16)
                  for k in keys[3:]]
        ins = {"Q": [q], "KNew": [new[0]], "VNew": [new[1]],
               "KCache": [caches[0]], "VCache": [caches[1]],
               "Position": [jnp.full((rows,), pos, jnp.int32)]}
        out = jax.jit(lambda ins: kernel(None, ins, {
            "num_heads": heads, "num_kv_heads": kv_heads,
            "diffusion_block": diffusion_block}))(ins)

        def dense(q, k_cache, v_cache):
            with jax.default_matmul_precision("highest"):
                qh = q.astype(jnp.float32).reshape(
                    rows, positions, kv_heads, group, dim)
                s = jnp.einsum("btkgd,bksd->bkgts", qh,
                               k_cache.astype(jnp.float32)) * dim ** -0.5
                reach = pos + (jnp.arange(positions) // diffusion_block
                               + 1) * diffusion_block
                s = jnp.where(jnp.arange(slots)[None, :] < reach[:, None],
                              s, -jnp.inf)
                o = jnp.einsum("bkgts,bksd->btkgd", jax.nn.softmax(s, -1),
                               v_cache.astype(jnp.float32))
            return o.reshape(rows, positions, heads * dim)

        want = np.asarray(jax.jit(dense)(q, out["KCacheOut"][0],
                                         out["VCacheOut"][0]))
        got = np.asarray(out["Out"][0], np.float32)
        err = float(np.abs(got - want).max() / np.abs(want).max())
        check(np.isfinite(got).all() and err < BF16_TOL,
              "cached_attention under diffusion_block %d, %d positions at "
              "%d: off the dense softmax by %.4f of its largest value"
              % (diffusion_block, positions, pos, err))
        print("  block-causal walk [%d, %d, %d x %d] over %d slots from "
              "%d: within %.4f of the dense softmax"
              % (rows, positions, heads, dim, slots, pos, err), flush=True)


def resnet50_serve(image_size=224, class_dim=1000, buckets=(1, 4, 16),
                   sizes=(1, 2, 4, 3, 8, 16, 5, 1)):
    import jax
    import paddle_tpu.fluid as fluid
    from paddle_tpu import models
    from paddle_tpu.tools import serve_cli

    fluid.amp.enable_bf16()
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        image = fluid.layers.data(
            name="image", shape=[3, image_size, image_size],
            dtype="float32")
        probs = fluid.layers.softmax(
            models.resnet50(image, class_dim=class_dim))
    model_dir = os.path.join(CHECKOUT, "build", "chip_smoke_model")
    server = None
    try:
        exe = fluid.Executor(fluid.TPUPlace(0))
        with fluid.scope_guard(fluid.Scope()):
            exe.run(startup)
            fluid.io.save_inference_model(
                model_dir, ["image"], [probs], exe,
                main_program=main.clone(for_test=True))

        server = serve_cli.start_server(serve_cli.parse_args([
            "--model_dir", model_dir, "--port", "0",
            "--max_batch", str(max(buckets)),
            "--batch_buckets", ",".join(map(str, buckets))]))
        engine = server.engine
        check(engine.last_warmup_stats is not None,
              "the server started without warming its buckets")
        on = engine.param_devices()
        check(on == {jax.devices()[0]},
              "engine parameters are on %s, not on %s"
              % (on, jax.devices()[0]))

        host, port = server.address
        conn = http.client.HTTPConnection(host, port, timeout=300)
        rs = np.random.RandomState(0)
        images = rs.rand(max(sizes), 3, image_size,
                         image_size).astype(np.float32).round(3)
        first = {}
        for n in sizes:
            conn.request(
                "POST", "/v1/infer",
                json.dumps({"inputs": {"image": images[:n].tolist()}}),
                {"Content-Type": "application/json"})
            resp = conn.getresponse()
            payload = json.loads(resp.read())
            check(resp.status == 200,
                  "POST /v1/infer of %d image(s) answered %d: %r"
                  % (n, resp.status, payload))
            out = np.asarray(payload["outputs"][engine.fetch_names[0]])
            check(out.shape == (n, class_dim) and np.isfinite(out).all(),
                  "%d image(s): bad output shape %s or non-finite"
                  % (n, out.shape))
            check(np.abs(out.sum(axis=1) - 1).max() < BF16_TOL,
                  "%d image(s): a row of probabilities does not sum "
                  "to 1 (%r)" % (n, out.sum(axis=1)))
            # image 0 leads every request.  Requests padded to the same
            # bucket run the same executable, where a sample's answer
            # does not depend on its neighbours: padding and slicing
            # must hand back the very same row
            same = first.setdefault(engine.config.bucket_for(n), out[0])
            check(np.abs(out[0] - same).max() < 1e-6,
                  "image 0 answered differently in a request of %d" % n)

        conn.request("GET", "/metrics")
        metrics = conn.getresponse().read().decode()
        conn.close()
        miss = re.search(r"^serving_compile_cache_miss_total (\S+)$",
                         metrics, re.M)
        hit = re.search(r"^serving_compile_cache_hit_total (\S+)$",
                        metrics, re.M)
        check(miss and hit, "/metrics lacks the compile-cache counters")
        check(float(miss.group(1)) == 0 and float(hit.group(1)) > 0,
              "/metrics shows a compile after warmup: %s miss(es), %s "
              "hit(s)" % (miss.group(1), hit.group(1)))
        print("  %d requests answered 200; compile-cache %s hit(s), 0 "
              "misses after warmup" % (len(sizes), hit.group(1)),
              flush=True)
    finally:
        if server is not None:
            server.shutdown()
        shutil.rmtree(model_dir, ignore_errors=True)


def multichip(n_devices=4, batch=512, image_size=224, class_dim=1000):
    import jax
    from jax.sharding import NamedSharding
    import paddle_tpu.fluid as fluid
    from paddle_tpu.parallel import make_mesh
    from paddle_tpu.parallel.sharding import batch_spec
    from paddle_tpu.spmd import SpmdTrainer

    fluid.amp.enable_bf16()
    mesh = make_mesh(n_devices=n_devices)
    main, startup, _, loss = build_image_model(
        "resnet50", batch, image_size, class_dim)
    trainer = SpmdTrainer(main, startup, feed_names=["image", "label"],
                          fetch_names=[loss.name], mesh=mesh)
    trainer.init()
    feeds = {
        n: jax.device_put(v, NamedSharding(
            mesh, batch_spec(v.shape, mesh)))
        for n, v in image_feeds(batch, image_size, class_dim).items()}
    for n, v in feeds.items():
        check(len(v.sharding.device_set) == n_devices,
              "feed %s is on %d device(s), not %d"
              % (n, len(v.sharding.device_set), n_devices))
    losses = [scalar(trainer.step(feeds)[0]) for _ in range(3)]
    check_falling("SpmdTrainer step", losses)
    for n, v in trainer.state.items():
        check(v.sharding.device_set == set(mesh.devices.flat),
              "state %s is on %s, not on the mesh" % (n, v.devices()))


# ---------------------------------------------------------------------------

def main():
    import jax

    devices = jax.devices()
    dev = devices[0]
    print("jax %s platform=%s device_kind=%s device_count=%d"
          % (jax.__version__, dev.platform, dev.device_kind,
             len(devices)), flush=True)
    if dev.platform != "tpu":
        print("chip_smoke: JAX found no TPU (platform %s)" % dev.platform,
              file=sys.stderr)
        return 1

    from paddle_tpu.utils.compile_cache import enable_compile_cache

    print("compile cache: %s" % enable_compile_cache(), flush=True)
    clock = CompileClock()
    phases = [resnet50_train, transformer_train, moe_experts_check,
              ssd_scan_check, selective_scan_check, block_causal_walk_check,
              resnet50_serve]
    if len(devices) >= 4:
        phases.append(multichip)
    for phase in phases:
        name = phase.__name__.replace("_", "-")
        print("phase %s ..." % name, flush=True)
        t0 = time.perf_counter()
        phase()
        seconds, hits, misses = clock.lap()
        print("phase %s: passed on platform=%s device_kind=%s — wall "
              "%.1fs, compile %.1fs, cache %d hit(s) %d miss(es)"
              % (name, dev.platform, dev.device_kind,
                 time.perf_counter() - t0, seconds, hits, misses),
              flush=True)
    if len(devices) < 4:
        print("multichip: not run, %d chip(s)" % len(devices), flush=True)

    from paddle_tpu import native

    check(native._lib is None, "something loaded the native runtime")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
