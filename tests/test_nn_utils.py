"""paddle.nn.utils (reference nn/utils/weight_norm_hook.py,
spectral_norm_hook.py, transform_parameters.py) and
paddle.nn.initializer 2.0 spellings."""
import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu import dygraph, nn


def test_weight_norm_reparameterizes_and_trains():
    with dygraph.guard():
        lyr = nn.Linear(4, 3)
        w0 = np.asarray(lyr.weight._value).copy()
        nn.utils.weight_norm(lyr, name="weight", dim=0)
        names = set(lyr._parameters)
        assert "weight" not in names and {"weight_g",
                                          "weight_v"} <= names
        x = pt.to_tensor(np.ones((2, 4), "f4"))
        y0 = np.asarray(lyr(x)._value)
        # w = g * v/||v|| reproduces the original weight at init
        ref = x._value @ w0
        np.testing.assert_allclose(
            y0, np.asarray(ref + lyr.bias._value), rtol=1e-5, atol=1e-6)
        # gradients reach the factors
        lyr(x).sum().backward()
        assert lyr._parameters["weight_g"].grad is not None
        assert lyr._parameters["weight_v"].grad is not None


def test_remove_weight_norm_bakes_value():
    with dygraph.guard():
        lyr = nn.Linear(4, 3)
        nn.utils.weight_norm(lyr)
        x = pt.to_tensor(np.ones((2, 4), "f4"))
        y_normed = np.asarray(lyr(x)._value)
        nn.utils.remove_weight_norm(lyr)
        assert "weight" in lyr._parameters
        assert "weight_g" not in lyr._parameters
        np.testing.assert_allclose(np.asarray(lyr(x)._value), y_normed,
                                   rtol=1e-5)


def test_weight_norm_dim_none_is_whole_tensor_norm():
    """dim in (None, -1): one scalar g over the whole tensor (reference
    norm_except_dim with dim=-1); forward still reproduces the original
    weight at init."""
    for dim in (None, -1):
        with dygraph.guard():
            lyr = nn.Linear(4, 3)
            w0 = np.asarray(lyr.weight._value).copy()
            nn.utils.weight_norm(lyr, name="weight", dim=dim)
            g = lyr._parameters["weight_g"]
            assert int(np.prod(g.shape)) == 1, g.shape
            np.testing.assert_allclose(
                float(np.asarray(g._value).reshape(())),
                np.sqrt((w0 * w0).sum() + 1e-12), rtol=1e-6)
            x = pt.to_tensor(np.ones((2, 4), "f4"))
            y = np.asarray(lyr(x)._value)
            ref = np.ones((2, 4), "f4") @ w0 + np.asarray(lyr.bias._value)
            np.testing.assert_allclose(y, ref, rtol=1e-5, atol=1e-6)


def test_weight_norm_negative_dim_counts_from_back():
    """dim=-2 on a rank-2 weight == dim 0 (dim % ndim), NOT whole-tensor."""
    with dygraph.guard():
        lyr = nn.Linear(4, 3)
        w0 = np.asarray(lyr.weight._value).copy()
        nn.utils.weight_norm(lyr, name="weight", dim=-2)
        g = np.asarray(lyr._parameters["weight_g"]._value)
        assert g.shape == (4, 1), g.shape  # per-dim-0 magnitudes
        np.testing.assert_allclose(
            g, np.sqrt((w0 * w0).sum(axis=1, keepdims=True) + 1e-12),
            rtol=1e-6)
        x = pt.to_tensor(np.ones((2, 4), "f4"))
        ref = np.ones((2, 4), "f4") @ w0 + np.asarray(lyr.bias._value)
        np.testing.assert_allclose(np.asarray(lyr(x)._value), ref,
                                   rtol=1e-5, atol=1e-6)


def test_spectral_norm_unit_top_singular_value():
    # a fixed weight: 20 power iterations reach 1e-3 only when the top
    # two singular values are apart, which a random init (the global
    # key, so whatever ran before on this worker) does not promise
    w = np.random.RandomState(0).randn(6, 5).astype("f4")
    with dygraph.guard():
        lyr = nn.Linear(6, 5, weight_attr=pt.ParamAttr(
            initializer=nn.initializer.Assign(w)))
        nn.utils.spectral_norm(lyr, n_power_iterations=20)
        x = pt.to_tensor(np.eye(6, dtype="f4"))
        lyr(x)  # trigger hook; layer.weight now normalized
        w = np.asarray(lyr.weight._value)
        s = np.linalg.svd(w, compute_uv=False)
        assert abs(s.max() - 1.0) < 1e-3, s.max()


def test_spectral_norm_grad_treats_uv_as_constants():
    """The power-iteration vectors are detached: for L = sum(W/sigma),
    dL/dW must equal 1/sigma - (sum(W)/sigma^2) * u v^T with u, v the
    post-iteration constants (reference spectral_norm_hook semantics)."""
    with dygraph.guard():
        lyr = nn.Linear(6, 5, bias_attr=False)
        W = np.asarray(lyr.weight._value).copy()
        nn.utils.spectral_norm(lyr, n_power_iterations=1)
        x = pt.to_tensor(np.eye(6, dtype="f4"))
        lyr(x).sum().backward()
        got = np.asarray(lyr._parameters["weight_orig"].grad._value)

        # numpy oracle with the SAME u0 (seeded buffer init) and one
        # power iteration, u/v held constant in the differentiation
        eps = 1e-12
        u = np.random.RandomState(0).randn(6).astype("f4")
        v = W.T @ u
        v = v / (np.linalg.norm(v) + eps)
        u = W @ v
        u = u / (np.linalg.norm(u) + eps)
        sigma = u @ W @ v
        want = np.full_like(W, 1.0 / sigma) \
            - (W.sum() / sigma**2) * np.outer(u, v)
        np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-5)


def test_spectral_norm_u_is_persistent_buffer():
    """u rides state_dict (reference registers it as a buffer), so the
    power-iteration state survives save/load instead of restarting."""
    with dygraph.guard():
        lyr = nn.Linear(6, 5)
        nn.utils.spectral_norm(lyr)
        x = pt.to_tensor(np.eye(6, dtype="f4"))
        for _ in range(5):
            lyr(x)  # advance the power iteration
        sd = lyr.state_dict()
        assert "weight_u" in sd
        u_trained = np.asarray(sd["weight_u"]._value).copy()

        lyr2 = nn.Linear(6, 5)
        nn.utils.spectral_norm(lyr2)
        missing, unexpected = lyr2.set_state_dict(sd)
        assert not missing and not unexpected, (missing, unexpected)
        np.testing.assert_allclose(
            np.asarray(lyr2._buffers["weight_u"]._value), u_trained)
        np.testing.assert_allclose(np.asarray(lyr2(x)._value),
                                   np.asarray(lyr(x)._value),
                                   rtol=1e-6)


def test_parameters_vector_roundtrip():
    with dygraph.guard():
        lyr = nn.Linear(3, 2)
        vec = nn.utils.parameters_to_vector(lyr.parameters())
        assert vec.shape == [3 * 2 + 2]
        new = pt.to_tensor(np.arange(8, dtype="f4"))
        nn.utils.vector_to_parameters(new, lyr.parameters())
        np.testing.assert_allclose(
            np.asarray(lyr.weight._value).ravel(), np.arange(6, dtype="f4"))
        np.testing.assert_allclose(np.asarray(lyr.bias._value),
                                   [6.0, 7.0])


def test_nn_initializer_namespace():
    from paddle_tpu.nn import initializer as I

    for cls in (I.Constant, I.Normal, I.Uniform, I.TruncatedNormal,
                I.XavierNormal, I.XavierUniform, I.KaimingNormal,
                I.KaimingUniform, I.Assign):
        assert cls is not None
    v = I.XavierUniform().eager_value((4, 4), "float32",
                                      __import__("jax").random.PRNGKey(0))
    lim = np.sqrt(6.0 / 8)
    assert float(np.abs(np.asarray(v)).max()) <= lim + 1e-6


@pytest.mark.parametrize("shape", [(7, 9, 3, 4), (10, 10, 3, 3),
                                   (5, 7, 5, 2)])
@pytest.mark.parametrize("mode", ["max", "avg"])
def test_adaptive_pool_non_divisible(shape, mode):
    """Arbitrary adaptive pooling sizes (reference AdaptivePool: cell i
    pools [floor(i*I/O), ceil((i+1)*I/O))); torch is the oracle."""
    import torch

    ih, iw, oh, ow = shape
    rs = np.random.RandomState(0)
    x = rs.randn(2, 3, ih, iw).astype("f4")
    t = torch.tensor(x)
    ref = (torch.nn.functional.adaptive_max_pool2d(t, (oh, ow))
           if mode == "max" else
           torch.nn.functional.adaptive_avg_pool2d(t, (oh, ow))).numpy()
    with dygraph.guard():
        lyr = (nn.AdaptiveMaxPool2D((oh, ow)) if mode == "max"
               else nn.AdaptiveAvgPool2D((oh, ow)))
        got = np.asarray(lyr(pt.to_tensor(x))._value)
    np.testing.assert_allclose(got, ref, rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("mode", ["bilinear", "nearest"])
@pytest.mark.parametrize("align_corners", [True, False])
def test_grid_sample_reflection_padding(mode, align_corners):
    """Reflection padding (reference grid_sampler_op.cc); torch is the
    oracle, incl. far-out-of-range coordinates."""
    import torch

    from paddle_tpu.dygraph import run_op
    from paddle_tpu.dygraph.tensor import Tensor

    rs = np.random.RandomState(0)
    x = rs.randn(2, 3, 6, 7).astype("f4")
    grid = (rs.rand(2, 5, 4, 2).astype("f4") * 3.0 - 1.5)
    ref = torch.nn.functional.grid_sample(
        torch.tensor(x), torch.tensor(grid), mode=mode,
        padding_mode="reflection", align_corners=align_corners).numpy()
    with dygraph.guard():
        out = run_op("grid_sampler",
                     {"X": Tensor(x), "Grid": Tensor(grid)},
                     {"mode": mode, "padding_mode": "reflection",
                      "align_corners": align_corners},
                     out_slots=("Output",))["Output"]
    np.testing.assert_allclose(np.asarray(out._value), ref,
                               rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("shape", [(7, 9, 3, 4), (5, 7, 5, 2)])
def test_adaptive_max_pool_with_index_non_divisible(shape):
    """max_pool2d_with_index adaptive non-divisible: values AND flat
    h*w argmax indices match torch's return_indices contract."""
    import torch

    from paddle_tpu.dygraph import run_op
    from paddle_tpu.dygraph.tensor import Tensor

    ih, iw, oh, ow = shape
    rs = np.random.RandomState(1)
    x = rs.randn(2, 3, ih, iw).astype("f4")
    ref, ridx = torch.nn.functional.adaptive_max_pool2d(
        torch.tensor(x), (oh, ow), return_indices=True)
    with dygraph.guard():
        res = run_op("max_pool2d_with_index", {"X": Tensor(x)},
                     {"ksize": [oh, ow], "adaptive": True},
                     out_slots=("Out", "Mask"))
    np.testing.assert_allclose(np.asarray(res["Out"]._value),
                               ref.numpy(), rtol=1e-6, atol=1e-7)
    np.testing.assert_array_equal(np.asarray(res["Mask"]._value),
                                  ridx.numpy())


def test_adaptive_max_pool3d_with_index_non_divisible():
    import torch

    from paddle_tpu.dygraph import run_op
    from paddle_tpu.dygraph.tensor import Tensor

    rs = np.random.RandomState(2)
    x = rs.randn(2, 2, 5, 7, 9).astype("f4")
    ref, ridx = torch.nn.functional.adaptive_max_pool3d(
        torch.tensor(x), (2, 3, 4), return_indices=True)
    with dygraph.guard():
        res = run_op("max_pool3d_with_index", {"X": Tensor(x)},
                     {"ksize": [2, 3, 4], "adaptive": True},
                     out_slots=("Out", "Mask"))
    np.testing.assert_allclose(np.asarray(res["Out"]._value),
                               ref.numpy(), rtol=1e-6, atol=1e-7)
    np.testing.assert_array_equal(np.asarray(res["Mask"]._value),
                                  ridx.numpy())
