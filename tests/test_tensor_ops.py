"""Op tests: tensor manipulation (reference: test_concat_op.py,
test_split_op.py, test_reshape_op.py, test_transpose_op.py,
test_expand_op.py, test_pad_op.py, test_crop_op.py, test_gather_op.py,
test_scatter_op.py, test_top_k_op.py, test_multiplex_op.py,
test_fill_*.py, test_assign_*.py, test_one_hot, test_lookup_table_op.py,
test_shape_op, test_im2sequence, test_bilinear_tensor_product_op.py)."""

import numpy as np
import pytest

from op_test import OpTest

RS = np.random.RandomState(11)


class TestConcat(OpTest):
    op_type = "concat"

    def test(self):
        xs = [("c%d" % i, RS.rand(2, 3).astype("float32"))
              for i in range(3)]
        self.inputs = {"X": xs}
        self.attrs = {"axis": 1}
        self.outputs = {"Out": np.concatenate([a for _, a in xs], axis=1)}
        self.check_output()
        self.check_grad(["c0", "c2"], "Out")


class TestSplit(OpTest):
    op_type = "split"

    def test(self):
        x = RS.rand(4, 6).astype("float32")
        parts = np.split(x, 3, axis=1)
        self.inputs = {"X": x}
        self.attrs = {"num": 3, "axis": 1}
        self.outputs = {"Out": [("s%d" % i, p)
                                for i, p in enumerate(parts)]}
        self.check_output()
        self.check_grad(["X"], "Out")


class TestSplitSections(OpTest):
    op_type = "split"

    def test(self):
        x = RS.rand(4, 6).astype("float32")
        self.inputs = {"X": x}
        self.attrs = {"sections": [1, 2, 3], "axis": 1}
        self.outputs = {"Out": [("t0", x[:, :1]), ("t1", x[:, 1:3]),
                                ("t2", x[:, 3:])]}
        self.check_output()


class TestReshape(OpTest):
    op_type = "reshape"

    def test(self):
        x = RS.rand(2, 6).astype("float32")
        self.inputs = {"X": x}
        self.attrs = {"shape": [3, -1]}
        self.outputs = {"Out": x.reshape(3, 4)}
        self.check_output()
        self.check_grad(["X"], "Out")


class TestTranspose(OpTest):
    op_type = "transpose"

    def test(self):
        x = RS.rand(2, 3, 4).astype("float32")
        self.inputs = {"X": x}
        self.attrs = {"axis": [1, 2, 0]}
        self.outputs = {"Out": x.transpose(1, 2, 0)}
        self.check_output()
        self.check_grad(["X"], "Out")


class TestExpand(OpTest):
    op_type = "expand"

    def test(self):
        x = RS.rand(2, 3).astype("float32")
        self.inputs = {"X": x}
        self.attrs = {"expand_times": [2, 2]}
        self.outputs = {"Out": np.tile(x, (2, 2))}
        self.check_output()
        self.check_grad(["X"], "Out")


class TestPad(OpTest):
    op_type = "pad"

    def test(self):
        x = RS.rand(2, 3).astype("float32")
        self.inputs = {"X": x}
        self.attrs = {"paddings": [1, 0, 0, 2], "pad_value": 0.5}
        self.outputs = {"Out": np.pad(x, [(1, 0), (0, 2)],
                                      constant_values=0.5)}
        self.check_output()
        self.check_grad(["X"], "Out")


class TestCrop(OpTest):
    op_type = "crop"

    def test(self):
        x = RS.rand(4, 5).astype("float32")
        self.inputs = {"X": x}
        self.attrs = {"offsets": [1, 2], "shape": [2, 3]}
        self.outputs = {"Out": x[1:3, 2:5]}
        self.check_output()
        self.check_grad(["X"], "Out")


def test_cast_to_the_type_another_variable_runs_in():
    """`cast(x, <Variable>)`: the type that variable holds as the
    Program runs, which a scope decides (bfloat16 weights under a
    Program that states float32), not the one the Program states."""
    import jax.numpy as jnp
    import paddle_tpu.fluid as fluid
    from paddle_tpu.ops.registry import get_op_info

    out = get_op_info("cast").kernel(
        None, {"X": [jnp.ones((2, 3), jnp.float32)],
               "Like": [jnp.zeros((1,), jnp.bfloat16)]},
        {"out_dtype": "float32"})["Out"][0]
    assert out.dtype == jnp.bfloat16 and out.shape == (2, 3)

    main = fluid.Program()
    with fluid.program_guard(main, fluid.Program()):
        a = fluid.layers.data(name="a", shape=[3], dtype="float32")
        b = fluid.layers.data(name="b", shape=[1], dtype="float32")
        c = fluid.layers.cast(a, b)
    op = main.global_block().ops[-1]
    assert op.type == "cast" and op.input("Like") == [b.name]
    assert c.dtype == b.dtype


class TestSlice(OpTest):
    op_type = "slice"

    @pytest.mark.parametrize("axes,starts,ends,want", [
        ([1], [-1], [2 ** 31 - 1], (slice(None), slice(-1, None))),
        ([0, 1], [1, -4], [3, -1], (slice(1, 3), slice(-4, -1))),
    ])
    @pytest.mark.parametrize("own_layout", [False, True])
    def test(self, axes, starts, ends, want, own_layout):
        """`own_layout` says where the result lies, not what it is."""
        x = RS.rand(4, 5).astype("float32")
        self.inputs = {"Input": x}
        self.attrs = {"axes": axes, "starts": starts, "ends": ends}
        if own_layout:
            self.attrs["own_layout"] = True
        self.outputs = {"Out": x[want]}
        self.check_output()
        self.check_grad(["Input"], "Out")


class TestCumsum(OpTest):
    op_type = "cumsum"

    @pytest.mark.parametrize("exclusive", [False, True])
    @pytest.mark.parametrize("axis", [0, 1])
    def test(self, axis, exclusive):
        x = RS.rand(3, 5).astype("float32")
        self.inputs = {"X": x}
        self.attrs = {"axis": axis, "exclusive": exclusive}
        self.outputs = {"Out": np.cumsum(x, axis=axis)
                        - (x if exclusive else 0)}
        self.check_output()
        self.check_grad(["X"], "Out")


class TestGather(OpTest):
    op_type = "gather"

    def test(self):
        x = RS.rand(6, 3).astype("float32")
        idx = np.asarray([1, 3, 5], dtype="int32")
        self.inputs = {"X": x, "Index": idx}
        self.outputs = {"Out": x[idx]}
        self.check_output()
        self.check_grad(["X"], "Out")


class TestScatter(OpTest):
    op_type = "scatter"

    def test(self):
        ref = RS.rand(5, 3).astype("float32")
        idx = np.asarray([1, 3], dtype="int32")
        upd = RS.rand(2, 3).astype("float32")
        out = ref.copy()
        out[idx] = upd
        self.inputs = {"Ref": ref, "Index": idx, "Updates": upd}
        self.outputs = {"Out": out}
        self.check_output()


class TestTopK(OpTest):
    op_type = "top_k"

    def test(self):
        x = RS.rand(4, 6).astype("float32")
        k = 2
        idx = np.argsort(-x, axis=1)[:, :k]
        vals = np.take_along_axis(x, idx, axis=1)
        self.inputs = {"X": x}
        self.attrs = {"k": k}
        self.outputs = {"Out": vals, "Indices": idx.astype("int64")}
        self.check_output()


class TestMultiplex(OpTest):
    op_type = "multiplex"

    def test(self):
        xs = [("m%d" % i, RS.rand(4, 3).astype("float32"))
              for i in range(3)]
        ids = RS.randint(0, 3, (4, 1)).astype("int32")
        out = np.stack([xs[ids[i, 0]][1][i] for i in range(4)])
        self.inputs = {"Ids": ids, "X": xs}
        self.outputs = {"Out": out}
        self.check_output()


class TestFillConstant(OpTest):
    op_type = "fill_constant"

    def test(self):
        self.inputs = {}
        self.attrs = {"shape": [3, 4], "value": 2.5, "dtype": "float32"}
        self.outputs = {"Out": np.full((3, 4), 2.5, "float32")}
        self.check_output()


class TestFillZerosLike(OpTest):
    op_type = "fill_zeros_like"

    def test(self):
        x = RS.rand(3, 4).astype("float32")
        self.inputs = {"X": x}
        self.outputs = {"Out": np.zeros_like(x)}
        self.check_output()


class TestFillConstantBatchSizeLike(OpTest):
    op_type = "fill_constant_batch_size_like"

    def test(self):
        x = RS.rand(5, 4).astype("float32")
        self.inputs = {"Input": x}
        self.attrs = {"shape": [-1, 7], "value": 1.5, "dtype": "float32"}
        self.outputs = {"Out": np.full((5, 7), 1.5, "float32")}
        self.check_output()


class TestAssign(OpTest):
    op_type = "assign"

    def test(self):
        x = RS.rand(3, 4).astype("float32")
        self.inputs = {"X": x}
        self.outputs = {"Out": x}
        self.check_output()


class TestOneHot(OpTest):
    op_type = "one_hot"

    def test(self):
        ids = RS.randint(0, 5, (4, 1)).astype("int64")
        out = np.zeros((4, 5), "float32")
        out[np.arange(4), ids.ravel()] = 1.0
        self.inputs = {"X": ids}
        self.attrs = {"depth": 5}
        self.outputs = {"Out": out}
        self.check_output()


class TestLookupTable(OpTest):
    op_type = "lookup_table"

    def test(self):
        table = RS.rand(10, 4).astype("float32")
        ids = RS.randint(0, 10, (5, 1)).astype("int64")
        self.inputs = {"W": table, "Ids": ids}
        self.outputs = {"Out": table[ids.ravel()]}
        self.check_output()
        self.check_grad(["W"], "Out")


class TestShapeOp(OpTest):
    op_type = "shape"

    def test(self):
        x = RS.rand(3, 4).astype("float32")
        self.inputs = {"Input": x}
        self.outputs = {"Out": np.asarray([3, 4], dtype="int64")}
        self.check_output()


class TestBilinearTensorProduct(OpTest):
    op_type = "bilinear_tensor_product"

    def test(self):
        b, m, n, o = 3, 4, 5, 2
        x = RS.rand(b, m).astype("float32")
        y = RS.rand(b, n).astype("float32")
        w = RS.rand(o, m, n).astype("float32")
        bias = RS.rand(1, o).astype("float32")
        out = np.einsum("bm,omn,bn->bo", x, w, y) + bias
        self.inputs = {"X": x, "Y": y, "Weight": w, "Bias": bias}
        self.outputs = {"Out": out.astype("float32")}
        self.check_output(atol=1e-4)
        self.check_grad(["X", "Y", "Weight", "Bias"], "Out",
                        max_relative_error=0.02)


class TestIm2Sequence(OpTest):
    op_type = "im2sequence"

    def test(self):
        # 1x1 kernel stride 1: output rows are just pixels scanned row-major
        x = RS.rand(1, 2, 3, 3).astype("float32")
        self.inputs = {"X": x}
        self.attrs = {"kernels": [1, 1], "strides": [1, 1],
                      "paddings": [0, 0, 0, 0]}
        out = x[0].transpose(1, 2, 0).reshape(9, 2)
        self.outputs = {"Out": (out, [[0, 9]])}
        self.check_output()


class TestFill(OpTest):
    op_type = "fill"

    def test(self):
        self.inputs = {}
        self.attrs = {"shape": [2, 3], "dtype": "float32",
                      "data": [1, 2, 3, 4, 5, 6]}
        self.outputs = {"Out": np.arange(1.0, 7.0, dtype="float32")
                        .reshape(2, 3)}
        self.check_output()
