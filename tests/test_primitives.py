"""Tests for activations, initializers, losses, updaters, flat-param utils."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu.nn.activations import ACTIVATIONS, get_activation
from deeplearning4j_tpu.nn.initializers import INITIALIZERS, get_initializer
from deeplearning4j_tpu.nn.losses import LOSSES, get_loss
from deeplearning4j_tpu.nn.updaters import (
    Adam, Nesterovs, Sgd, StepSchedule, build_optimizer, get_updater,
)
from deeplearning4j_tpu.util.params import (
    flat_to_params, num_params, params_to_flat,
)


class TestActivations:
    def test_known_values(self):
        x = jnp.array([-2.0, -0.5, 0.0, 0.5, 2.0])
        np.testing.assert_allclose(get_activation("relu")(x),
                                   [0, 0, 0, 0.5, 2.0])
        np.testing.assert_allclose(get_activation("identity")(x), x)
        np.testing.assert_allclose(get_activation("hardtanh")(x),
                                   [-1, -0.5, 0, 0.5, 1.0])
        np.testing.assert_allclose(get_activation("cube")(x),
                                   [-8, -0.125, 0, 0.125, 8.0], rtol=1e-6)

    def test_softmax_normalizes(self):
        x = jax.random.normal(jax.random.PRNGKey(0), (4, 10))
        y = get_activation("softmax")(x)
        np.testing.assert_allclose(jnp.sum(y, axis=-1), jnp.ones(4), rtol=1e-5)

    @pytest.mark.parametrize("name", sorted(ACTIVATIONS))
    def test_all_finite_and_differentiable(self, name):
        x = jnp.linspace(-3, 3, 32).reshape(4, 8)
        fn = get_activation(name)
        y = fn(x)
        assert y.shape == x.shape
        assert jnp.all(jnp.isfinite(y))
        g = jax.grad(lambda a: jnp.sum(fn(a)))(x)
        assert jnp.all(jnp.isfinite(g))


class TestInitializers:
    @pytest.mark.parametrize("name", [n for n in sorted(INITIALIZERS)
                                      if n != "identity"])
    def test_shapes_and_scale(self, name):
        key = jax.random.PRNGKey(42)
        w = get_initializer(name)(key, (64, 32), 64, 32)
        assert w.shape == (64, 32)
        assert jnp.all(jnp.isfinite(w))

    def test_xavier_variance(self):
        key = jax.random.PRNGKey(1)
        w = get_initializer("xavier")(key, (500, 500), 500, 500)
        expected_std = np.sqrt(2.0 / 1000)
        assert abs(float(jnp.std(w)) - expected_std) < 0.1 * expected_std

    def test_identity(self):
        w = get_initializer("identity")(jax.random.PRNGKey(0), (5, 5), 5, 5)
        np.testing.assert_allclose(w, jnp.eye(5))


class TestLosses:
    def test_mse_known(self):
        labels = jnp.array([[1.0, 2.0]])
        preout = jnp.array([[1.5, 2.5]])
        assert abs(float(get_loss("mse")(labels, preout)) - 0.25) < 1e-6

    def test_mcxent_matches_manual(self):
        labels = jnp.array([[0.0, 1.0, 0.0]])
        logits = jnp.array([[0.1, 2.0, -1.0]])
        expected = -jax.nn.log_softmax(logits)[0, 1]
        got = get_loss("mcxent")(labels, logits, "softmax")
        np.testing.assert_allclose(got, expected, rtol=1e-6)

    def test_sparse_equals_dense_mcxent(self):
        logits = jax.random.normal(jax.random.PRNGKey(0), (8, 5))
        idx = jnp.array([0, 1, 2, 3, 4, 0, 1, 2])
        dense = jax.nn.one_hot(idx, 5)
        a = get_loss("mcxent")(dense, logits, "softmax")
        b = get_loss("sparse_mcxent")(idx, logits, "softmax")
        np.testing.assert_allclose(a, b, rtol=1e-6)

    def test_xent_stable_at_extremes(self):
        labels = jnp.array([[1.0], [0.0]])
        z = jnp.array([[100.0], [-100.0]])
        v = get_loss("xent")(labels, z, "sigmoid")
        assert jnp.isfinite(v) and float(v) < 1e-4

    def test_mask_zeroes_contribution(self):
        labels = jnp.ones((2, 3, 4))
        preout = jnp.zeros((2, 3, 4))
        mask = jnp.array([[1.0, 1.0, 0.0], [1.0, 0.0, 0.0]])
        full = get_loss("mse")(labels, preout, "identity")
        masked = get_loss("mse")(labels, preout, "identity", mask=mask)
        np.testing.assert_allclose(masked, full, rtol=1e-6)  # same per-step err

    @pytest.mark.parametrize("name", sorted(LOSSES))
    def test_all_losses_differentiable(self, name):
        key = jax.random.PRNGKey(3)
        if name == "sparse_mcxent":
            labels = jnp.array([0, 1, 2, 3])
        elif name in ("hinge", "squared_hinge"):
            labels = jnp.sign(jax.random.normal(key, (4, 4)))
        else:
            labels = jax.nn.softmax(jax.random.normal(key, (4, 4)))
        preout = jax.random.normal(jax.random.PRNGKey(4), (4, 4))
        fn = get_loss(name)
        g = jax.grad(lambda z: fn(labels, z))(preout)
        assert jnp.all(jnp.isfinite(g))


class TestUpdaters:
    def test_resolve(self):
        assert isinstance(get_updater("adam"), Adam)
        assert isinstance(get_updater(("sgd", 0.5)), Sgd)
        assert get_updater(("sgd", 0.5)).learning_rate == 0.5

    def test_sgd_step(self):
        tx = build_optimizer(Sgd(learning_rate=0.1))
        params = {"w": jnp.ones(3)}
        st = tx.init(params)
        grads = {"w": jnp.ones(3)}
        updates, _ = tx.update(grads, st, params)
        np.testing.assert_allclose(updates["w"], -0.1 * jnp.ones(3), rtol=1e-6)

    def test_schedule(self):
        s = StepSchedule(initial=1.0, decay_rate=0.5, step=10).to_optax()
        assert s(0) == 1.0
        assert s(10) == 0.5
        assert s(25) == 0.25

    def test_nesterov_converges_quadratic(self):
        tx = build_optimizer(Nesterovs(learning_rate=0.05, momentum=0.9))
        params = {"w": jnp.array([5.0])}
        st = tx.init(params)
        for _ in range(100):
            g = {"w": 2 * params["w"]}
            up, st = tx.update(g, st, params)
            params = jax.tree_util.tree_map(lambda p, u: p + u, params, up)
        assert abs(float(params["w"][0])) < 1e-2


class TestFlatParams:
    def test_roundtrip(self):
        params = {"0": {"W": jnp.arange(6.0).reshape(2, 3), "b": jnp.ones(3)},
                  "1": {"W": jnp.full((3, 2), 2.0)},
                  "10": {"b": jnp.zeros(2)}}
        flat = params_to_flat(params)
        assert flat.shape == (num_params(params),)
        back = flat_to_params(flat, params)
        for k in params:
            for p in params[k]:
                np.testing.assert_allclose(back[k][p], params[k][p])

    def test_canonical_order_numeric(self):
        params = {"2": {"a": jnp.array([2.0])}, "10": {"a": jnp.array([10.0])},
                  "1": {"a": jnp.array([1.0])}}
        flat = params_to_flat(params)
        np.testing.assert_allclose(flat, [1.0, 2.0, 10.0])


class TestHostCast:
    """_as_jnp host-side 16-bit cast: halves H2D bytes for bf16 compute
    and must be bit-identical to the transfer-then-device-cast path."""

    @staticmethod
    def _spy_transfer_dtype(monkeypatch):
        """Record the dtype of whatever _as_jnp hands to jnp.asarray —
        the observable that distinguishes host-cast from device-cast."""
        import deeplearning4j_tpu.nn.fit_loop as ml
        seen = {}
        real = ml.jnp.asarray

        def spy(a, *args, **kwargs):
            seen["dtype"] = getattr(a, "dtype", None)
            return real(a, *args, **kwargs)

        monkeypatch.setattr(ml.jnp, "asarray", spy)
        return seen

    def test_bf16_host_cast_bitwise_matches_device_cast(self, monkeypatch):
        from deeplearning4j_tpu.nn.fit_loop import _as_jnp
        monkeypatch.setenv("DL4J_TPU_HOST_CAST", "1")
        seen = self._spy_transfer_dtype(monkeypatch)
        rs = np.random.RandomState(0)
        a = (rs.randn(64, 17) * 100).astype(np.float32)
        host = _as_jnp(a, jnp.dtype(jnp.bfloat16))
        assert seen["dtype"] == jnp.bfloat16      # cast BEFORE transfer
        dev = jnp.asarray(a).astype(jnp.bfloat16)
        assert host.dtype == jnp.bfloat16
        np.testing.assert_array_equal(
            np.asarray(host).view(np.uint16),
            np.asarray(dev).view(np.uint16))

    def test_kill_switch_and_non_16bit_paths(self, monkeypatch):
        from deeplearning4j_tpu.nn.fit_loop import _as_jnp
        monkeypatch.setenv("DL4J_TPU_HOST_CAST", "1")
        seen = self._spy_transfer_dtype(monkeypatch)
        a = np.ones((3, 3), np.float32)
        # f32 compute: no host cast, dtype preserved
        out = _as_jnp(a, jnp.dtype(jnp.float32))
        assert out.dtype == jnp.float32
        assert seen["dtype"] == np.float32
        # masks (dtype=None): untouched
        assert _as_jnp(a).dtype == jnp.float32
        # f64 sources must NOT host-cast (double-rounding via f32 differs)
        _as_jnp(np.ones((2, 2), np.float64), jnp.dtype(jnp.bfloat16))
        assert seen["dtype"] == np.float64
        monkeypatch.setenv("DL4J_TPU_HOST_CAST", "0")
        out = _as_jnp(a, jnp.dtype(jnp.bfloat16))
        assert seen["dtype"] == np.float32        # transferred as f32...
        assert out.dtype == jnp.bfloat16          # ...cast on device
