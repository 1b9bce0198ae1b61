"""Times the grouped-product kernels (kernels/grouped_matmul.py) on the
chip, bfloat16 with float32 accumulation, one line a variant.

`olmoe`: against XLA's own `ragged_dot` at the shapes of the cell
olmoe-train-4k, 32768 rows ordered by 64 experts, [2048 -> 1024] and
[1024 -> 2048]: milliseconds a call (host clock around 20 calls that end
in block_until_ready) and the share of the bf16 peak the required FLOPs
make of it; with `sweep` also the tilings beside the one the kernel
chooses.

`shares`: the forward product where a chip holds 16 experts of 256 and
few rows reach them (`moe_experts`' range form: the sizes sum below the
rows).  dsv32-turn-16k-ep16's [128, 7168] x [16, 7168, 2048] and [128,
2048] x [16, 2048, 7168] with 0, 1, 2, 4, 6, 8 and 16 experts that got
one row each, and pangu-decode-ep16's [2048, 7680] x [16, 7680, 2048]
with 8 rows on each of 16: milliseconds a call (host clock around one
program that makes 100 calls in a scan, so the list of visits' own
operations are in it and the host's dispatch is not), the share of the
HBM peak that the bytes of the experts *that have a row* make of it, and
the largest difference from `ragged_dot` over the rows that are a
group's.

`python scripts/moe_gmm_bench.py [olmoe] [sweep] [shares]`; both parts
when none is named.
"""

import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, ".")
from paddle_tpu.kernels import grouped_matmul as G  # noqa: E402

M, D, F, E = 32768, 2048, 1024, 64
PEAK = 197e12
HBM = 819e9
CALLS = 20
SCANNED = 100


def timed(fn, *args):
    fn = jax.jit(fn)
    out = jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(CALLS):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / CALLS * 1e3, out


def scanned(call, a, b, counts):
    """ms a call of `call(a, b, counts)` inside one program that makes
    SCANNED of them, and the last call's result."""
    def many(a, b, all_counts):
        def body(_, c):
            return call(a, b, c), None
        return jax.lax.scan(body, jnp.zeros((a.shape[0], b.shape[2]),
                                            a.dtype), all_counts)[0]
    many = jax.jit(many)
    all_counts = jnp.broadcast_to(counts, (SCANNED,) + counts.shape)
    out = jax.block_until_ready(many(a, b, all_counts))
    t0 = time.perf_counter()
    out = jax.block_until_ready(many(a, b, all_counts))
    return (time.perf_counter() - t0) / SCANNED * 1e3, out


def shares():
    """The forward product of an expert layer's share, by how many of
    its 16 experts got a row."""
    bf = jnp.bfloat16
    cases = [("dsv32 gate/up", 128, 7168, 2048, 1, (0, 1, 2, 4, 6, 8, 16)),
             ("dsv32 down", 128, 2048, 7168, 1, (0, 1, 2, 4, 6, 8, 16)),
             ("pangu gate/up", 2048, 7680, 2048, 8, (16,))]
    for name, m, k, n, rows, groups in cases:
        ks = jax.random.split(jax.random.PRNGKey(k), 2)
        x = jax.random.normal(ks[0], (m, k), bf)
        w = jax.random.normal(ks[1], (16, k, n), bf) * 0.02
        blocks = G.choose_blocks(m, k, n, 2, "fwd")
        for held in groups:
            sizes = np.zeros(16, np.int32)
            # the experts with a row, spread evenly over the 16
            sizes[np.round(np.linspace(0, 15, held)).astype(int)] = rows
            counts = jnp.asarray(sizes)
            owned = int(sizes.sum())
            ms, got = scanned(
                lambda a, b, c: G._rows_call("fwd", blocks, a, b, c),
                x, w, counts)
            want = G.ragged_gmm(x, w, counts)
            off = float(jnp.max(jnp.abs(
                got[:owned].astype(jnp.float32)
                - want[:owned].astype(jnp.float32)))) if owned else 0.0
            due = held * k * n * 2 + m * k * 2 + m * n * 2
            print("%-14s [%d, %d] x [16, %d, %d] kernel %s  %2d experts x "
                  "%d rows  %8.4f ms  %5.1f%% of the HBM peak (%.1f MB "
                  "due)  max|diff| %.3g"
                  % (name, m, k, k, n, blocks, held, rows, ms,
                     due / (ms * 1e-3) / HBM * 100, due / 1e6, off),
                  flush=True)


def main():
    named = [a for a in sys.argv[1:] if a in ("olmoe", "shares")]
    dev = jax.devices()[0]
    print("platform=%s kind=%s" % (dev.platform, dev.device_kind))
    if not named or "shares" in named:
        shares()
    if not named or "olmoe" in named:
        olmoe("sweep" in sys.argv[1:])


def olmoe(sweep):
    key = jax.random.PRNGKey(0)
    ks = jax.random.split(key, 6)
    bf = jnp.bfloat16
    x = jax.random.normal(ks[0], (M, D), bf)
    h = jax.random.normal(ks[1], (M, F), bf)
    w_up = jax.random.normal(ks[2], (E, D, F), bf) * 0.02
    w_down = jax.random.normal(ks[3], (E, F, D), bf) * 0.02
    rng = np.random.RandomState(0)
    routings = {
        "uniform": np.bincount(rng.randint(0, E, M), minlength=E),
        "skewed": np.bincount(
            rng.choice(E, M, p=np.arange(1, E + 1) / (E * (E + 1) / 2)),
            minlength=E),
    }
    flops = 2.0 * M * D * F
    cases = {
        "fwd_up": (x, w_up), "fwd_down": (h, w_down),
        "dx_up": (h, w_up), "dx_down": (x, w_down),
        "dw_up": (x, h), "dw_down": (h, x),
    }
    plain = {"fwd": G.ragged_gmm,
             # w^T first: the form XLA's TPU ragged product takes natively
             "dx": lambda dy, w, c: G.ragged_gmm(dy, jnp.swapaxes(w, 1, 2), c),
             "dx_as_lowered": G.ragged_gmm_dx,
             "dw": G.ragged_gmm_dw}
    # (block_m, block_n, block_k) beside the chosen one; the contraction
    # (k of fwd, n of dx) stays whole
    tilings = {
        "fwd_up": [(128, F, D), (256, F // 2, D), (512, F, D)],
        "fwd_down": [(128, D, F), (256, D // 2, F), (512, D, F)],
        "dx_up": [(128, F, D), (256, F, D // 2), (512, F, D)],
        "dx_down": [(128, D, F), (256, D, F // 2), (512, D, F)],
        "dw_up": [(128, F, D), (512, F, D), (256, F // 2, D),
                  (256, F, D // 2), (1024, F, D // 2)],
        "dw_down": [(128, D, F), (512, D, F), (256, D // 2, F),
                    (256, D, F // 2), (1024, D // 2, F)],
    }
    for routing, counts in routings.items():
        counts = jnp.asarray(counts, jnp.int32)
        print("routing %s: rows an expert min %d max %d"
              % (routing, int(counts.min()), int(counts.max())))
        for case, (a, b) in cases.items():
            kind = case.split("_")[0]
            k, n = (b.shape[1:] if kind != "dw"
                    else (a.shape[1], b.shape[1]))
            chosen = G.choose_blocks(a.shape[0], k, n, 2, kind)
            variants = [chosen]
            if sweep and routing == "uniform":
                variants += [t for t in tilings[case] if t != chosen]
            want = None
            names = [kind] + (["dx_as_lowered"] if kind == "dx" else [])
            for name in names:
                ms, want = timed(plain[name], a, b, counts)
                print("%-9s %-28s %8.3f ms  %5.1f%% of peak"
                      % (case, "ragged_dot" + name[len(kind):], ms,
                         flops / (ms * 1e-3) / PEAK * 100), flush=True)
            for blocks in variants:
                label = "kernel %s" % (blocks,)
                call = {"fwd": lambda a, b, c, t=blocks:
                        G._rows_call("fwd", t, a, b, c),
                        "dx": lambda a, b, c, t=blocks:
                        G._rows_call("dx", t, a, b, c),
                        "dw": lambda a, b, c, t=blocks:
                        G._dw_call(t, a, b, c)}[kind]
                try:
                    ms, got = timed(call, a, b, counts)
                except Exception as err:  # a tiling Mosaic refuses
                    print("%-9s %-28s refused: %s"
                          % (case, label, str(err).splitlines()[0][:90]),
                          flush=True)
                    continue
                off = float(jnp.max(jnp.abs(got.astype(jnp.float32)
                                            - want.astype(jnp.float32))))
                print("%-9s %-28s %8.3f ms  %5.1f%% of peak  max|diff| "
                      "%.3g" % (case, label, ms,
                                flops / (ms * 1e-3) / PEAK * 100, off),
                      flush=True)


if __name__ == "__main__":
    main()
