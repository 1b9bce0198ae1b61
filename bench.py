"""Benchmark suite: training throughput on one chip.

Mirrors the reference benchmark set (reference: benchmark/paddle/image/
{resnet,alexnet,vgg,googlenet,smallnet_mnist_cifar}.py + run.sh and
benchmark/paddle/rnn/rnn.py) on the BASELINE.json north-star metric.
BENCH_MODEL selects the model (default resnet50 — the driver's
headline); vs_baseline compares against the strongest published
in-tree number for that model (BASELINE.md tables).

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline",
"step_ms", "mfu", "amp_bf16", "platform", "device_kind",
"device_count"} — the device as JAX reports it.

The run is on the accelerator or it is nothing: when JAX finds only the
CPU and the caller did not ask for it in so many words
(JAX_PLATFORMS=cpu, the smoke gate's tiny shapes), bench.py exits
non-zero and prints no record.  This process holds the chip from start
to end and starts no child.
"""

import json
import os
import sys
import time

import numpy as np

# Image-model FLOPs are computed exactly from the built program IR
# (fluid/analysis.py program_costs — matches XLA's per-HLO FLOP
# accounting); lstm/transformer use closed-form per-run models below.
# Baselines: BASELINE.md (IntelOptimizedPaddle.md CPU img/s tables and
# benchmark/README.md K40m ms/batch converted to img/s at batch 128).
_MODELS = {
    # infer_baseline: reference MKL-DNN inference img/s at batch 16
    # (/root/reference/benchmark/IntelOptimizedPaddle.md:68-104); vgg16
    # has no published row (the reference measured vgg19)
    "resnet50": dict(baseline=82.35, unit="img/s",
                     infer_baseline=217.69),
    "alexnet": dict(baseline=498.94, unit="img/s",
                    infer_baseline=850.51),
    "vgg16": dict(baseline=29.83, unit="img/s", infer_baseline=None),
    "vgg19": dict(baseline=29.83, unit="img/s", infer_baseline=96.75),
    "googlenet": dict(baseline=264.83, unit="img/s",
                      infer_baseline=600.94),
    "smallnet": dict(baseline=7039.0, unit="img/s", infer_baseline=None),
    # no reference baseline (the benchmark set has no MNIST conv row);
    # the ptune selftest's flagship: tiny enough to measure on CPU
    "lenet5": dict(baseline=None, unit="img/s", infer_baseline=None),
    # strongest published LSTM number: batch 256, hidden 256 on
    # K40m = 170 ms/batch -> 1506 samples/s (BASELINE.md:26);
    # compare like-for-like with BENCH_BATCH=256 BENCH_HIDDEN=256
    "lstm": dict(baseline=1506.0, unit="samples/s"),
    # no reference counterpart (the 2018 snapshot has no transformer):
    # exercises the pallas flash-attention op through the Program
    # stack; vs_baseline is null by design
    "transformer": dict(baseline=None, unit="tokens/s"),
}


def _image_spec(model):
    """Per-image-model channels/image-size/class-dim defaults.

    ONE table, owned by paddle_tpu.tune.models — the tuner ranks the
    program this file measures, so a default that drifted between two
    hand-maintained copies would silently price one program and time
    another."""
    from paddle_tpu.tune.models import MODELS

    return MODELS[model]


def _image_model_fn(model):
    from paddle_tpu import models

    return {"resnet50": models.resnet50, "alexnet": models.alexnet,
            "vgg16": models.vgg16, "vgg19": models.vgg19,
            "googlenet": models.googlenet, "lenet5": models.lenet5,
            "smallnet": models.smallnet_mnist_cifar}[model]


def _build_image_model(model, batch, image_size, class_dim):
    from __graft_entry__ import _build_model

    return _build_model(_image_model_fn(model), batch, image_size,
                        class_dim, with_loss=True,
                        channels=_image_spec(model)["channels"])


def _image_feeds(batch, image_size, class_dim, channels=3):
    rs = np.random.RandomState(0)
    image = rs.rand(batch, channels, image_size,
                    image_size).astype(np.float32)
    label = rs.randint(0, class_dim, size=(batch, 1)).astype(np.int64)
    return {"image": image, "label": label}


def _build_lstm(batch, seq_len, dict_dim, hidden):
    import paddle_tpu.fluid as fluid
    from paddle_tpu.models.text import stacked_lstm_text_classifier

    main = fluid.Program()
    startup = fluid.Program()
    with fluid.program_guard(main, startup):
        data = fluid.layers.data(name="words", shape=[1], dtype="int64",
                                 lod_level=1)
        probs = stacked_lstm_text_classifier(data, dict_dim,
                                             hid_dim=hidden)
        label = fluid.layers.data(name="label", shape=[1], dtype="int64")
        loss = fluid.layers.mean(
            x=fluid.layers.cross_entropy(input=probs, label=label))
        fluid.optimizer.Adam(learning_rate=1e-3).minimize(loss)
    return main, startup, loss


def _lstm_feeds(batch, seq_len, dict_dim):
    from paddle_tpu.core.ragged import RaggedTensor

    rs = np.random.RandomState(0)
    seqs = [rs.randint(0, dict_dim, size=(seq_len, 1)).astype(np.int64)
            for _ in range(batch)]
    words = RaggedTensor.from_sequences(seqs)
    label = rs.randint(0, 2, size=(batch, 1)).astype(np.int64)
    return {"words": words, "label": label}


def functional_step(main_prog, feed_names, fetch_name, scope, dev):
    """(step, state) — the step this file times: the whole program
    through FunctionalProgram under one jax.jit, every state array on
    `dev` and donated to the call.  chip_smoke.py drives the same
    function."""
    import jax
    from paddle_tpu.analysis.alias import state_donation
    from paddle_tpu.fluid.executor import RNG_STATE_NAME
    from paddle_tpu.jit import FunctionalProgram, state_from_scope

    fp = FunctionalProgram(main_prog, feed_names, [fetch_name])
    state = {n: jax.device_put(np.asarray(v), dev)
             for n, v in state_from_scope(fp, scope).items()}
    # stochastic ops (alexnet/vgg dropout) draw from a state-carried key
    state[RNG_STATE_NAME] = jax.device_put(jax.random.PRNGKey(0), dev)
    step = jax.jit(lambda s, f: fp(s, f),
                   donate_argnums=(0,) if state_donation() else ())
    return step, state


def _append_history(record):
    """Every emitted record also joins the perf-history trajectory
    (perf_history.jsonl next to this file) so `pperf gate` sees the
    full run-to-run story.
    BENCH_HISTORY=<path> redirects, BENCH_HISTORY=0 disables;
    BENCH_LEG (set by mega_bench) names the leg in the history line."""
    dest = os.environ.get("BENCH_HISTORY", "")
    if dest == "0":
        return
    path = dest or os.path.join(
        os.path.dirname(os.path.abspath(__file__)),
        "perf_history.jsonl")
    try:
        from paddle_tpu.obs import perf as obs_perf

        obs_perf.append_history(record, path,
                                leg=os.environ.get("BENCH_LEG"))
    except Exception as exc:  # noqa: BLE001 — history must not kill
        print("bench: history append failed: %r" % (exc,),
              file=sys.stderr, flush=True)


def _tagged(metric, recompute_stride=0, micro=1, prefetch=0):
    """BENCH_TAG distinguishes variant runs of one config in the
    perf history and the emitted metric (e.g. the
    FLAGS_fuse_optimizer=0 A/B: ...batch128+nofuse); an ACTIVE
    recompute rewrite (the effective stride, parsed once in main) tags
    as +rcp<stride>, a micro-batch split as +mb<m>, a device-prefetch
    input pipeline as +pf<depth>."""
    tag = os.environ.get("BENCH_TAG", "")
    parts = ([tag] if tag else []) + \
        (["rcp%d" % recompute_stride] if recompute_stride else []) + \
        (["mb%d" % micro] if micro > 1 else []) + \
        (["pf%d" % prefetch] if prefetch else []) + \
        (["nhwc"] if os.environ.get("BENCH_LAYOUT") == "NHWC" else [])
    return metric + "".join("+" + p for p in parts)


def _config_blob(model, mode, batch, micro, rcp, amp_bf16, pass_spec,
                 image_size=None, prefetch=0):
    """The candidate-point blob stamped into every BENCH record and
    history line, so a tuner measurement (paddle_tpu.tune) joins back
    to the config that produced it without filename archaeology.
    `mesh` is the tuner's candidate mesh (BENCH_MESH) — informational
    on a single-chip run; `pass_pipeline` is the compile-cache
    pipeline id the FLAGS_compile_passes spec resolves to."""
    pipeline = None
    if pass_spec:
        from paddle_tpu.compile.passes import pipeline_id

        pipeline = pipeline_id(pass_spec) or None
    blob = {
        "model": model, "mode": mode, "batch": batch,
        "micro_batches": micro,
        "mesh": os.environ.get("BENCH_MESH") or None,
        "pass_pipeline": pipeline,
        "amp_bf16": amp_bf16,
        "recompute": rcp,
        "prefetch": prefetch,
        "layout": os.environ.get("BENCH_LAYOUT", "NCHW"),
        "tag": os.environ.get("BENCH_TAG") or None,
    }
    if image_size is not None:
        blob["image_size"] = image_size
    return blob


def main():
    import jax
    from paddle_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    dev = jax.devices()[0]
    if dev.platform == "cpu" and os.environ.get("JAX_PLATFORMS") != "cpu":
        raise SystemExit(
            "bench: JAX found no accelerator (platform cpu); set "
            "JAX_PLATFORMS=cpu to run on the CPU on purpose")
    if os.environ.get("BENCH_MULTICHIP"):
        # MULTICHIP legs: SPMD scaling across mesh shapes (img/s +
        # MFU + timed comm vs the plan's ring floor), records stamped
        # with platform_class — paddle_tpu/spmd/bench.py owns the
        # whole suite, including history appends
        from paddle_tpu.spmd import bench as spmd_bench

        raise SystemExit(spmd_bench.main_from_env())
    if os.environ.get("BENCH_SERVING"):
        # SERVING leg: open-loop load against a loopback server; the
        # record's `latency` blob (p50..p99.9 + SLO attainment) is
        # what `pperf gate --latency-tolerance` regresses on
        from paddle_tpu.obs import load as obs_load

        record = obs_load.run_serving_bench()
        print(json.dumps(record))
        _append_history(record)
        return
    model = os.environ.get("BENCH_MODEL", "resnet50")
    if model not in _MODELS:
        raise SystemExit("BENCH_MODEL must be one of %s"
                         % sorted(_MODELS))
    # BENCH_MODE=infer times the deploy path: the inference clone of the
    # model run through FunctionalProgram (the InferenceEngine
    # equivalent, paddle_tpu/jit.py), batch 16 like the reference's
    # inference tables
    mode = os.environ.get("BENCH_MODE", "train")
    if mode not in ("train", "infer"):
        raise SystemExit("BENCH_MODE must be train or infer")
    if mode == "infer" and model in ("lstm", "transformer"):
        raise SystemExit("BENCH_MODE=infer supports the image models")
    spec = _MODELS[model]
    default_batch = ("16" if mode == "infer"
                     else "16" if model == "transformer" else "128")
    batch = int(os.environ.get("BENCH_BATCH", default_batch))
    # effective recompute stride: train-only (the rewrite targets the
    # backward region); parsed once so the metric tag and the rewrite
    # can never disagree
    try:
        rcp = int(os.environ.get("BENCH_RECOMPUTE", "0"))
    except ValueError:
        raise SystemExit("BENCH_RECOMPUTE must be an integer stride")
    if rcp < 0:
        raise SystemExit("BENCH_RECOMPUTE must be >= 0")
    if mode != "train":
        rcp = 0
    # BENCH_MICRO_BATCH=m: μ-cuDNN-style split — build the model at
    # batch/m and run m sequential micro-steps per logical step (the
    # memory-vs-speed knob the tuner searches; activations scale 1/m)
    try:
        micro = int(os.environ.get("BENCH_MICRO_BATCH", "1"))
    except ValueError:
        raise SystemExit("BENCH_MICRO_BATCH must be an integer split")
    if micro < 1:
        raise SystemExit("BENCH_MICRO_BATCH must be >= 1")
    if micro > 1:
        if mode != "train" or model in ("lstm", "transformer"):
            raise SystemExit("BENCH_MICRO_BATCH supports image-model "
                             "training")
        if batch % micro:
            raise SystemExit("BENCH_BATCH=%d not divisible by "
                             "BENCH_MICRO_BATCH=%d" % (batch, micro))
    # BENCH_PREFETCH=depth: feed every step through an async
    # device-prefetch reader (reader/prefetch.device_prefetch) instead
    # of a pinned device-resident constant — a worker thread prepares
    # and device_puts the NEXT batch while the current step runs.
    # This is the lever for input-bound verdicts (AlexNet at 14% MFU):
    # the measurement finally includes a per-step H2D input cost, and
    # the prefetch depth is what hides it.  0 (default) keeps the old
    # device-resident-feeds loop.
    try:
        prefetch = int(os.environ.get("BENCH_PREFETCH", "0"))
    except ValueError:
        raise SystemExit("BENCH_PREFETCH must be an integer depth")
    if prefetch < 0:
        raise SystemExit("BENCH_PREFETCH must be >= 0")
    warmup = int(os.environ.get("BENCH_WARMUP", "3"))
    iters = int(os.environ.get("BENCH_ITERS",
                               "10" if mode == "train" else "30"))

    import paddle_tpu.fluid as fluid
    from paddle_tpu.obs import telemetry as obs_tele
    from paddle_tpu.utils import flags as pt_flags

    # bf16 MXU compute with f32 master weights is the TPU-native
    # training dtype (BENCH_AMP=0 for pure f32)
    amp_bf16 = os.environ.get("BENCH_AMP", "1") != "0"
    if amp_bf16:
        fluid.amp.enable_bf16()

    samples_per_step = batch
    if model == "lstm":
        seq_len = int(os.environ.get("BENCH_SEQ_LEN", "100"))
        hidden = int(os.environ.get("BENCH_HIDDEN", "256"))
        dict_dim = int(os.environ.get("BENCH_DICT_DIM", "10000"))
        main_prog, startup, avg_loss = _build_lstm(batch, seq_len,
                                                   dict_dim, hidden)
        feed_names = ["words", "label"]
        feeds_np = _lstm_feeds(batch, seq_len, dict_dim)
        flops_model = "closed-form"
        metric = "lstm_train_samples_per_sec_batch%d_hidden%d" \
            % (batch, hidden)
        # stacked-lstm matmul FLOPs per sample: fc1 (emb128->4H) +
        # 2 recurrent H->4H projections + the layer-2 fc over [4H, H],
        # x2 MACs, x3 fwd+bwd
        gflop_per_sample = 3 * 8 * seq_len * hidden * \
            (128 + 7 * hidden) / 1e9
    elif model == "transformer":
        from paddle_tpu.models.transformer_program import (
            build_transformer_program, transformer_program_feeds)

        seq_len = int(os.environ.get("BENCH_SEQ_LEN", "512"))
        d_model = int(os.environ.get("BENCH_D_MODEL", "512"))
        n_layer = int(os.environ.get("BENCH_N_LAYER", "6"))
        n_head = int(os.environ.get("BENCH_N_HEAD", "8"))
        vocab = int(os.environ.get("BENCH_VOCAB", "8192"))
        main_prog, startup, avg_loss, _ = build_transformer_program(
            batch, seq_len, vocab, n_layer=n_layer, n_head=n_head,
            d_model=d_model)
        with fluid.program_guard(main_prog, startup):
            fluid.optimizer.MomentumOptimizer(
                learning_rate=0.01, momentum=0.9).minimize(avg_loss)
        feed_names = ["tokens", "positions", "targets"]
        feeds_np = transformer_program_feeds(batch, seq_len, vocab)
        flops_model = "closed-form"
        metric = "transformer_train_tokens_per_sec_batch%d_seq%d_d%d" \
            % (batch, seq_len, d_model)
        # per token, fwd+bwd (x3): ~12*L*d^2 matmul MACs x2, the causal
        # attention score+context matmuls (T/2 attended keys on average
        # -> T*d MACs x2 per layer), and the vocab projection (d*V MACs
        # x2)
        gflop_per_sample = 3 * (24 * n_layer * d_model ** 2
                                + 2 * n_layer * seq_len * d_model
                                + 2 * d_model * vocab) / 1e9
        samples_per_step = batch * seq_len
    else:
        img_spec = _image_spec(model)
        image_size = int(os.environ.get("BENCH_IMAGE_SIZE",
                                        img_spec["image_size"]))
        class_dim = int(os.environ.get("BENCH_CLASS_DIM",
                                       img_spec["class_dim"]))
        metric = "%s_%s_imgs_per_sec_batch%d" % (model, mode, batch)
        # the build batch is the micro-batch slice; the logical step
        # still processes `batch` samples (m micro-steps per step)
        build_batch = batch // micro
        feeds_np = _image_feeds(build_batch, image_size, class_dim,
                                channels=img_spec["channels"])
        if mode == "infer":
            from __graft_entry__ import _build_model

            main_prog, startup, logits, _ = _build_model(
                _image_model_fn(model), build_batch, image_size,
                class_dim, with_loss=False,
                channels=img_spec["channels"])
            main_prog = main_prog.clone(for_test=True)
            avg_loss = logits
            feed_names = ["image"]
            feeds_np = {"image": feeds_np["image"]}
        else:
            main_prog, startup, _, avg_loss = _build_image_model(
                model, build_batch, image_size, class_dim)
            feed_names = ["image", "label"]
        # exact FLOPs from the built IR (fluid/analysis.py) rather than
        # a hand-maintained constant: fwd-only for the inference clone,
        # fwd+dgrad+wgrad for training, any image size — and the count
        # matches XLA's own per-HLO accounting, so `mfu` here reads
        # against the profile tables directly
        from paddle_tpu.fluid.analysis import program_costs

        step_flops = sum(f for _, f, _, _ in program_costs(main_prog))
        gflop_per_sample = step_flops / 1e9 / build_batch
        flops_model = "ir-2flops-per-mac"

    # BENCH_RECOMPUTE=<stride>: rematerialize forward segments in the
    # backward (fluid/recompute.py) — the HBM lever for big-batch runs
    if rcp:
        from paddle_tpu.fluid.recompute import (recompute_program,
                                                auto_checkpoints)
        cloned = recompute_program(
            main_prog, auto_checkpoints(main_prog, every=rcp))
        print("bench: recompute stride %d cloned %d forward ops"
              % (rcp, cloned), file=sys.stderr, flush=True)

    # FLAGS_compile_passes: the timed program dispatches through
    # FunctionalProgram (not the executor), so the tuner's pass
    # pipeline must be applied here for the measurement to cover it
    pass_spec = pt_flags.get_flag("compile_passes")
    if pass_spec:
        from paddle_tpu.compile.passes import optimize_program

        main_prog, _pm = optimize_program(main_prog, pass_spec,
                                          fetches=[avg_loss.name])
        print("bench: pass pipeline %s applied to the timed program"
              % _pm.pipeline_id, file=sys.stderr, flush=True)

    scope = fluid.Scope()
    exe = fluid.Executor(fluid.TPUPlace(0))
    exe.run(startup, scope=scope)

    step, state = functional_step(main_prog, feed_names, avg_loss.name,
                                  scope, dev)
    if prefetch:
        from paddle_tpu.reader.prefetch import device_prefetch

        def _batches():
            while True:
                yield feeds_np

        _feed_iter = iter(device_prefetch(_batches, place=None,
                                          depth=prefetch)())

        def next_feeds():
            return next(_feed_iter)
    else:
        feeds = jax.device_put(feeds_np, dev)

        def next_feeds():
            return feeds

    # AOT the steady-state step and keep the artifact: bootstrap
    # through the jit path until the state signature reaches its
    # fixed point (AMP casts state tensors on first touch and the
    # optimizer's velocity slots take one step MORE to settle — f32 ->
    # bf16 -> f32 — so lowering after a single step pins a transient
    # signature whose executable rejects the steady state on the
    # second timed call), then lower THAT signature once — the same
    # executable runs the remaining warmup + timed loop AND exposes
    # XLA's whole-step memory/cost analyses for the record's perf
    # blob.  The bootstrap compiles are the ones the jit path always
    # paid for the same signatures; the jax compilation cache absorbs
    # them on accelerator runs.  BENCH_AOT=0 opts out.
    xla_stats = {}
    # micro-batch split: m micro-steps per logical step, in both the
    # warmup and the timed loop (timed quantity = full-batch steps)
    warmup_steps = warmup * micro
    if warmup and os.environ.get("BENCH_AOT", "1") != "0":
        def _sig(s):
            return {n: (str(v.dtype), tuple(v.shape))
                    for n, v in s.items()}

        prev_sig = _sig(state)
        for _ in range(3):
            fetches, state = step(state, next_feeds())
            jax.block_until_ready(fetches)
            warmup_steps = max(warmup_steps - 1, 0)
            cur_sig = _sig(state)
            if cur_sig == prev_sig:
                break
            prev_sig = cur_sig
        from paddle_tpu.obs import health as obs_health

        step = step.lower(state, next_feeds()).compile()
        xla_stats = obs_health.publish_compile_stats(
            "bench/step", step) or {}

    for _ in range(warmup_steps):
        fetches, state = step(state, next_feeds())
    jax.block_until_ready(state)

    t0 = time.perf_counter()
    for _ in range(iters * micro):
        fetches, state = step(state, next_feeds())
    jax.block_until_ready(fetches)
    dt = time.perf_counter() - t0

    samples_per_sec = samples_per_step * iters / dt
    step_ms = dt / iters * 1e3
    # MFU denominator: the chip's published bf16 peak, looked up by
    # device_kind (or BENCH_PEAK_TFLOPS, said out loud by the caller);
    # a device that is not in the table has no mfu, never a default
    from paddle_tpu.fluid.analysis import DEVICE_PEAKS

    peak_tflops = float(os.environ.get("BENCH_PEAK_TFLOPS", 0)) or \
        DEVICE_PEAKS.get(dev.device_kind, {}).get("bf16_tflops")
    mfu = (None if gflop_per_sample is None or not peak_tflops
           else round(samples_per_sec * gflop_per_sample
                      / (peak_tflops * 1e3), 4))
    baseline = (spec["baseline"] if mode == "train"
                else spec.get("infer_baseline"))
    # the perf blob: measured step vs its roofline + the bottleneck
    # verdict (obs/perf.py) — every BENCH record carries its own
    # attribution instead of waiting for a hand-run roofline sweep
    perf_blob = None
    try:
        from paddle_tpu.obs import perf as obs_perf

        # the program is the micro-batch slice, so classify its own
        # per-micro step against its floors (micro=1: the full step)
        perf_blob = obs_perf.leg_perf_blob(
            main_prog, dt / (iters * micro),
            bf16_act=amp_bf16 and pt_flags.get_flag("amp_bf16_act"),
            peak_tflops=peak_tflops,
            hbm_gbps=float(os.environ.get("BENCH_HBM_GBPS", "0"))
            or None,
            xla_flops=xla_stats.get("xla_flops"),
            xla_bytes=xla_stats.get("xla_bytes_accessed"))
    except Exception as exc:  # noqa: BLE001 — a blob failure must
        print("bench: perf blob failed: %r" % (exc,),   # not eat the
              file=sys.stderr, flush=True)              # measurement
    # the memory blob: static liveness peak vs the AOT artifact's XLA
    # memory_analysis footprint + the device watermark (obs/mem.py) —
    # every record carries its HBM story so `pperf gate
    # --mem-tolerance` can fail an HBM regression like a step-time
    # one.  BENCH_MEMORY=0 opts out.
    mem_blob = None
    donation_blob = None
    if os.environ.get("BENCH_MEMORY", "1") != "0":
        try:
            from paddle_tpu.obs import mem as obs_mem

            mem_blob = obs_mem.bench_memory_blob(
                main_prog, fetches=[avg_loss.name],
                xla_stats=xla_stats)
        except Exception as exc:  # noqa: BLE001 — same contract as
            print("bench: memory blob failed: %r" % (exc,),  # perf
                  file=sys.stderr, flush=True)
        # the donation blob: what the alias analysis planned vs what
        # the flag/backend let through (planned/donated/declined
        # bytes + per-A-code decline attribution) — the record says
        # whether this run's step actually reused its state HBM
        try:
            from paddle_tpu.obs import mem as obs_mem

            donation_blob = obs_mem.bench_donation_blob(
                main_prog, fetches=[avg_loss.name])
        except Exception as exc:  # noqa: BLE001 — same contract
            print("bench: donation blob failed: %r" % (exc,),
                  file=sys.stderr, flush=True)
    metric = _tagged(metric, rcp, micro, prefetch)
    record = {
        "metric": metric,
        "value": round(samples_per_sec, 2),
        "unit": spec["unit"],
        "vs_baseline": (None if baseline is None
                        else round(samples_per_sec / baseline, 3)),
        "step_ms": round(step_ms, 2),
        "mfu": mfu,
        # which FLOP accounting `mfu` uses: records without this field
        # predate the exact IR count (their image-model mfu runs ~2x
        # low — the old constants were MAC counts)
        "flops_model": None if mfu is None else flops_model,
        "amp_bf16": amp_bf16,
        # the device as JAX reports it, not the requested one
        "platform": dev.platform,
        "device_kind": dev.device_kind,
        "device_count": len(jax.devices()),
        "perf": perf_blob,
        "memory": mem_blob,
        "donation": donation_blob,
        # the candidate point this record measured (tune/fit.py joins
        # history rows back to their plan entry through this)
        "config": _config_blob(
            model, mode, batch, micro, rcp, amp_bf16, pass_spec,
            image_size=None if model in ("lstm", "transformer")
            else image_size, prefetch=prefetch),
    }
    # what JAX's persistent compilation cache served this run (ci.sh
    # asserts the warm rerun shows hits)
    cc = obs_tele.snapshot()
    record["compile_cache"] = {
        "hits": cc.get("compile_cache_hits_total", 0),
        "misses": cc.get("compile_cache_misses_total", 0),
    }
    print(json.dumps(record))
    _append_history(record)


if __name__ == "__main__":
    main()
