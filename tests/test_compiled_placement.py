"""What the TPU's compiler makes of a decode step's chosen-set attention,
read from the text it compiles for a described v5e (no chip: the
`on-chip-measurement` guide, section 2; the topology is described inside
a fixture, and this is the one file that does so): `mla_cached_attention`
with `Selected` at one position a row, one layer in a scan that carries
the cache as a decoder's does, at the two chooser cells' shapes.

What PR 70 bought and this guards at no chip time: the gather of the
chosen latents (`dsa_gather`, a custom fusion) writes fast memory
(`S(1)`), and its one reader is the Pallas call `mla_decode_k2048`, which
takes the rows as the gather leaves them: no transposing `copy` of the
[batch, 2048, 576] set, no fill pass behind the gather and no float32
score array stand between (before, the step's gather filled in behind
itself, `take_along_axis`'s default, and the compiler made of that a
turn of the 37.7 MB set, `{2,1,0}` -> `{1,2,0}`, in front of two plain
products: a second copy in fast memory, 0.29 ms a step of
`dsv32-turn-16k-ep16`; the op's gather clips now).  A one-layer scan
places as `dsv32-turn-16k-ep16`'s and `hy4-turn-32k-ep16`'s whole
generation calls do (PERF.md section 6, PR 70, step (a)).

And of a Mamba-2 step: `ssd_scan` with `State` at one position a row at
granite-decode-ep4's shape, one layer in a scan that carries the state.
What PR 72 bought and this guards: the step is the Pallas call
`ssd_step_r64_b4`, whose state operand is the scan's carry itself and
whose state result is the next carry, one buffer (`kernels/ssd_step.py`'s
`input_output_aliases`): no `copy` of the 268 MB state stands before or
after it, with the rows a served step carries out of the state it was
handed read beside it (two rows' first heads, a head at a time: the
slice says `own_layout`, without which the compiler lays the whole
state out anew every step for that transpose's sake, `{1,2,0}`, 0.83 ms
a layer of `granite-decode-ep4`: PERF.md section 6, PR 72).

And of generation by diffusion over blocks (`models/decode.py
block_diffusion_decode`), whose rule (`_unmask`) reduces a pass's logits
where the head's product left them: the compiled call holds no float32
array with the vocabulary's extent, of a later pass's [rows, B] positions
or of a block's first pass's [rows, 2B]; the rule's reducing fusions (max
with argmax, the sum of exponentials) take the product's bfloat16
[rows x T, vocab] result itself; and no `reshape` or `copy` makes another
array of that size (before, the rule cast `[rows, B, vocab]`, whose tiles
hold B positions in 8 or 16 sublanes, so the cast was a relayout: two
float32 copies and three relayouts a pass, 2.35 s of a 19.45 s call of
`sdar-diffuse-pp8`: PERF.md section 6, PR 75)."""

import math
import os
import re

import pytest

import jax
import jax.numpy as jnp
from jax import lax

TOP_K, LATENT, ROPE = 2048, 512, 64


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip("no v5e:2x2 topology can be described here: %s" % e)
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def no_compile_cache():
    """A compile for a described chip is written to the persistent cache
    and cannot be read back without one: off around it."""
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _compiled_scan(chip, rows, heads, slots, nope, value, sink):
    """The text of 64 chosen-set steps of one layer, the cache carried."""
    from paddle_tpu.ops import registry

    def of(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=chip)

    ins = {"QNope": [of((rows, 1, heads * nope))],
           "QRope": [of((rows, 1, heads * ROPE))],
           "CNew": [of((rows, 1, LATENT))], "RNew": [of((rows, 1, ROPE))],
           "WUk": [of((LATENT, heads * nope))],
           "WUv": [of((LATENT, heads * value))],
           "Selected": [of((rows, TOP_K), jnp.int32)],
           "Live": [of((rows,), jnp.int32)]}
    if sink:
        ins["Sink"] = [of((heads,), jnp.float32)]
    kernel = registry.get_op_info("mla_cached_attention").kernel

    def steps(ins, cache):
        def body(carry, i):
            cache, seen = carry
            outs = kernel(None, dict(
                ins, Cache=[cache],
                Position=[jnp.full((rows,), slots - 1024 + i, jnp.int32)]),
                {"num_heads": heads})
            return (outs["CacheOut"][0],
                    seen + outs["Out"][0].astype(jnp.float32)), None
        return lax.scan(
            body, (cache, jnp.zeros((rows, 1, heads * value), jnp.float32)),
            jnp.arange(64))[0]

    return jax.jit(steps).lower(
        ins, of((rows, slots, LATENT + ROPE))).compile().as_text()


_INSTRUCTION = re.compile(
    r"^\s*(?:ROOT )?%([\w.\-]+) = (\(.*?\)|\S+) ([\w\-]+)\((.*?)\)(?:, (.*))?$")


def _instructions(text):
    """{name: (type, opcode, operand names, the rest of the line)}."""
    found = {}
    for line in text.splitlines():
        m = _INSTRUCTION.match(line)
        if m:
            name, kind, op, operands, rest = m.groups()
            found[name] = (kind, op, re.findall(r"%([\w.\-]+)", operands),
                           rest or "")
    return found


@pytest.mark.parametrize("cell,rows,heads,slots,nope,value,sink", [
    ("dsv32-turn-16k-ep16", 16, 128, 16384, 128, 128, False),
    ("hy4-turn-32k-ep16", 8, 64, 32768, 192, 256, True)])
def test_nothing_stands_between_a_steps_gather_and_its_reader(
        one_chip, no_compile_cache, cell, rows, heads, slots, nope, value,
        sink):
    text = _compiled_scan(one_chip, rows, heads, slots, nope, value, sink)
    found = _instructions(text)
    readers = [n for n, (_, op, _, rest) in found.items()
               if op == "custom-call" and "mla_decode_k2048" in rest]
    assert len(readers) == 1, cell
    # the kernel's operands: the position, the queries, the cache, a sink
    operands = found[readers[0]][2]
    assert len(operands) == 3 + sink
    at, through = operands[2], []
    while found[at][1] == "bitcast":
        through.append(at)
        at = found[at][2][0]
    kind, op, _, rest = found[at]
    gathered = "bf16[%d,%d]" % (rows * TOP_K, LATENT + ROPE)
    assert op == "fusion" and "kind=kCustom" in rest \
        and "dsa_gather" in rest and kind.startswith(gathered), (cell, at)
    # fast memory, the gather's result and the kernel's own
    assert "S(1)" in kind and "S(1)" in found[through[0]][0], cell
    assert "S(1)" in found[readers[0]][0], cell
    # and no other instruction makes an array of the gathered set's
    # size: no turn, no fill pass, no second copy (inside the gather's
    # own fusion its steps carry no memory space of their own)
    whole = "bf16[%d,%d,%d]" % (rows, TOP_K, LATENT + ROPE)
    others = [n for n, (kind, op, _, _) in found.items()
              if kind.startswith((whole, gathered)) and n != at
              and op not in ("bitcast", "parameter", "gather", "transpose",
                             "reshape")]
    assert not others, (cell, others)
    assert "f32[%d,%d,%d]" % (rows, heads, TOP_K) not in text, cell


def _compiled_state_scan(chip, rows, heads, dim, entries):
    """The text of 8 steps of one Mamba-2 layer, the state carried."""
    from paddle_tpu.ops import registry

    def of(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=chip)

    ins = {"X": [of((rows, 1, heads * dim))],
           "Dt": [of((rows, 1, heads))],
           "B": [of((rows, 1, entries))], "C": [of((rows, 1, entries))],
           "DtBias": [of((heads,), jnp.float32)],
           "ALog": [of((heads,), jnp.float32)],
           "D": [of((heads,), jnp.float32)]}
    kernel = registry.get_op_info("ssd_scan").kernel

    cut = registry.get_op_info("slice").kernel

    def steps(ins, state):
        def body(carry, _):
            state, seen, _ = carry
            # the next step's x is this one's y, as a layer's stream is
            outs = kernel(None, dict(ins, X=[seen], State=[state]),
                          {"num_heads": heads, "chunk_size": 256})
            # what models/hybrid_program.py carries out of the state a
            # step was handed: two rows' first 16 heads, a head at a time
            handed = cut(None, {"Input": [state]}, {
                "axes": [0, 2], "starts": [0, 0], "ends": [2, 16 * dim],
                "own_layout": True})["Out"][0]
            handed = jnp.transpose(handed.reshape(2, entries, 16, dim),
                                   (0, 2, 3, 1))
            return (outs["StateOut"][0], outs["Y"][0], handed), None
        return lax.scan(
            body, (state, ins["X"][0],
                   jnp.zeros((2, 16, dim, entries), jnp.float32)),
            jnp.arange(8))[0]

    return jax.jit(steps, donate_argnums=(1,)).lower(
        ins, of((rows, entries, heads * dim), jnp.float32)) \
        .compile().as_text()


def test_a_mamba_steps_state_is_the_scans_carry(one_chip, no_compile_cache):
    rows, heads, dim, entries = 64, 128, 64, 128
    lines = _compiled_state_scan(one_chip, rows, heads, dim,
                                 entries).splitlines()
    state = "f32[%d,%d,%d]" % (rows, entries, heads * dim)
    calls = [line for line in lines
             if " custom-call(" in line and "ssd_step_r64_b4" in line]
    assert len(calls) == 1
    results, rest = calls[0].split(" custom-call(", 1)
    # the state is the last operand and the second result, in HBM (no
    # memory space said), and the result is the operand's buffer
    assert results.count(state) == 1 \
        and state + "{2,1,0:T(8,128)})" in results
    assert "output_to_operand_aliasing={{1}: (4, {})}" in rest
    carried = re.findall(r"%([\w.\-]+)", rest.split(")", 1)[0])[-1]
    # the operand is the loop's carry as it arrives, the result leaves
    # as it is: nothing makes a second array of the state's size
    made = {m.group(1): m.group(2) for m in (
        re.match(r"\s*(?:ROOT )?%%([\w.\-]+) = %s\S* ([\w\-]+)\("
                 % re.escape(state), line) for line in lines) if m}
    assert made[carried] == "get-tuple-element"
    assert set(made.values()) <= {"parameter", "get-tuple-element",
                                  "bitcast"}, made


def _arrays(text):
    """`_instructions` of a compiled module outside its fusions' own
    computations: what writes an array of its own (inside a fusion
    nothing does)."""
    fused = set(re.findall(r"calls=%([\w.\-]+)", text))
    kept, keep = [], True
    for line in text.splitlines():
        m = re.match(r"^(?:ENTRY )?%([\w.\-]+) \(.*\{$", line)
        if m:
            keep = m.group(1) not in fused
        elif keep:
            kept.append(line)
    return _instructions("\n".join(kept))


def test_the_rule_reduces_the_logits_where_the_head_left_them(
        one_chip, no_compile_cache):
    from paddle_tpu.models import decode

    # a vocabulary that no other axis shares, rows x T that no slice of
    # the head has
    rows, block, vocab, hidden, extent = 24, 4, 2176, 256, 64

    def of(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def call(embed, head, state, prompt):
        def step(state, tokens):
            width = tokens.shape[1]
            slots = state["pos"][:, None] + jnp.arange(width)
            seen = state["seen"].at[jnp.arange(rows)[:, None], slots].set(
                embed[tokens])
            # a position's logits from every position stored so far
            stream = embed[tokens] + jnp.mean(seen, axis=1, keepdims=True)
            # the `mul` op's product: the positions of every row flat,
            # accumulated in float32 and handed on in the step's type
            logits = jnp.dot(stream.reshape(rows * width, hidden), head,
                             preferred_element_type=jnp.float32)
            return logits.astype(jnp.bfloat16).reshape(rows, width, vocab), {
                "pos": state["pos"] + width, "seen": seen}
        return decode.block_diffusion_decode(
            step, state, prompt, 16, block, 4, vocab - 1)

    text = jax.jit(call).lower(
        of((vocab, hidden)), of((hidden, vocab)),
        {"pos": of((rows,), jnp.int32), "seen": of((rows, extent, hidden))},
        of((rows, 8), jnp.int32)).compile().as_text()
    made = _arrays(text)
    wide = {n: kind for n, (kind, *_) in made.items()
            if re.match(r"\w+\[(\d+,)+%d\]" % vocab, kind)}
    # no float32 array of the vocabulary's extent, in any shape
    assert not [n for n, kind in wide.items() if kind.startswith("f32")], wide
    # of a pass's size ([rows x T, vocab], [rows, T, vocab]) the call
    # holds the head's product in the step's type, a later pass's and a
    # block's first pass's, and nothing else: no `reshape`, no `copy`, no
    # second form of the logits
    sized = {}
    for n, kind in wide.items():
        *lead, _ = (int(d) for d in kind[kind.index("[") + 1:
                                         kind.index("]")].split(","))
        width, rest = divmod(math.prod(lead), rows)
        if not rest and width in (block, 2 * block) \
                and made[n][1] not in ("parameter", "get-tuple-element",
                                       "bitcast"):
            sized[n] = width
    assert sorted(sized.values()) == [block, 2 * block], \
        {n: made[n][:2] for n in sized}
    for n, width in sized.items():
        kind, op, _, rest = made[n]
        assert kind.startswith("bf16[%d,%d]{" % (rows * width, vocab)) \
            and op == "fusion" and "kind=kOutput" in rest, made[n]
        # read by the rule's two reducing fusions and by nothing else
        readers = {m: made[m] for m in made if n in made[m][2]}
        assert len(readers) == 2, (n, readers)
        for _, op, _, rest in readers.values():
            assert op == "fusion" and decode.UNMASK_SCOPE in rest \
                and "reduce" in rest, (n, readers)
