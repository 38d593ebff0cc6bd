"""End-to-end MultiLayerNetwork tests: the minimum slice of SURVEY.md §7
build order — config -> init -> fit -> eval -> serialize."""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu.data.dataset import DataSet
from deeplearning4j_tpu.data.iterator import ArrayDataSetIterator
from deeplearning4j_tpu.nn.conf import (
    InputType, MultiLayerConfiguration, NeuralNetConfiguration,
)
from deeplearning4j_tpu.nn.layers import (
    LSTM, BatchNormalization, ConvolutionLayer, DenseLayer, OutputLayer,
    RnnOutputLayer, SubsamplingLayer,
)
from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
from deeplearning4j_tpu.nn.updaters import Adam, Nesterovs, Sgd
from deeplearning4j_tpu.train.listeners import CollectScoresIterationListener
from deeplearning4j_tpu.util.serialization import (
    load_model, restore_multilayer_network, save_model,
)


def make_blobs(n=256, nc=3, nf=4, seed=0):
    rng = np.random.default_rng(seed)
    centers = rng.normal(size=(nc, nf)) * 4
    X, Y = [], []
    for c in range(nc):
        X.append(rng.normal(size=(n // nc, nf)) + centers[c])
        y = np.zeros((n // nc, nc))
        y[:, c] = 1
        Y.append(y)
    X = np.concatenate(X).astype(np.float32)
    Y = np.concatenate(Y).astype(np.float32)
    idx = rng.permutation(len(X))
    return X[idx], Y[idx]


def mlp_conf(nf=4, nc=3, updater=None):
    return (NeuralNetConfiguration.Builder()
            .seed(42)
            .updater(updater or Adam(1e-2))
            .list()
            .layer(DenseLayer(n_out=32, activation="relu"))
            .layer(DenseLayer(n_out=32, activation="relu"))
            .layer(OutputLayer(n_out=nc, activation="softmax", loss="mcxent"))
            .set_input_type(InputType.feed_forward(nf))
            .build())


class TestMLP:
    def test_fit_reduces_score_and_learns(self):
        X, Y = make_blobs()
        net = MultiLayerNetwork(mlp_conf()).init()
        scores = CollectScoresIterationListener()
        net.set_listeners(scores)
        net.fit(ArrayDataSetIterator(X, Y, batch_size=64), epochs=30)
        first = scores.scores[0][1]
        last = scores.scores[-1][1]
        assert last < first * 0.5, f"loss did not drop: {first} -> {last}"
        ev = net.evaluate(ArrayDataSetIterator(X, Y, batch_size=64))
        assert ev.accuracy() > 0.9

    def test_output_shape_and_softmax(self):
        X, Y = make_blobs(n=30)
        net = MultiLayerNetwork(mlp_conf()).init()
        out = net.output(X)
        assert out.shape == (30, 3)
        np.testing.assert_allclose(np.sum(np.asarray(out), axis=1),
                                   np.ones(30), rtol=1e-5)

    def test_feed_forward_collects_all_activations(self):
        X, _ = make_blobs(n=16)
        net = MultiLayerNetwork(mlp_conf()).init()
        acts = net.feed_forward(X[:4])
        assert len(acts) == 3
        assert acts[0].shape == (4, 32)
        assert acts[-1].shape == (4, 3)

    def test_num_params(self):
        net = MultiLayerNetwork(mlp_conf()).init()
        # 4*32+32 + 32*32+32 + 32*3+3 = 160 + 1056 + 99
        assert net.num_params() == 4 * 32 + 32 + 32 * 32 + 32 + 32 * 3 + 3

    def test_params_flat_roundtrip(self):
        net = MultiLayerNetwork(mlp_conf()).init()
        flat = net.params_flat()
        assert flat.shape == (net.num_params(),)
        X, _ = make_blobs(n=16)
        before = np.asarray(net.output(X[:4]))
        net.set_params_flat(flat)
        after = np.asarray(net.output(X[:4]))
        np.testing.assert_allclose(before, after, rtol=1e-6)

    def test_l2_regularization_increases_score(self):
        X, Y = make_blobs(n=64)
        conf_plain = mlp_conf()
        conf_l2 = (NeuralNetConfiguration.Builder().seed(42).updater(Adam(1e-2))
                   .l2(0.1).list()
                   .layer(DenseLayer(n_out=32, activation="relu"))
                   .layer(DenseLayer(n_out=32, activation="relu"))
                   .layer(OutputLayer(n_out=3))
                   .set_input_type(InputType.feed_forward(4)).build())
        ds = DataSet(X, Y)
        n1 = MultiLayerNetwork(conf_plain).init()
        n2 = MultiLayerNetwork(conf_l2).init()
        assert n2.score(ds) > n1.score(ds)


class TestCNN:
    def test_lenet_slice_trains(self):
        """Minimum end-to-end slice: LeNet-style CNN on synthetic 'MNIST'
        (SURVEY.md §7 build order step 3; reference LeNet.java:83-95)."""
        rng = np.random.default_rng(0)
        n, nc = 128, 4
        X = rng.normal(size=(n, 12, 12, 1)).astype(np.float32)
        # separable-by-class data: class = quadrant with max energy
        labels = np.argmax([
            np.abs(X[:, :6, :6, 0]).sum((1, 2)),
            np.abs(X[:, :6, 6:, 0]).sum((1, 2)),
            np.abs(X[:, 6:, :6, 0]).sum((1, 2)),
            np.abs(X[:, 6:, 6:, 0]).sum((1, 2))], axis=0)
        Y = np.eye(nc, dtype=np.float32)[labels]
        conf = (NeuralNetConfiguration.Builder()
                .seed(1).updater(Adam(3e-3))
                .list()
                .layer(ConvolutionLayer(n_out=8, kernel=(3, 3),
                                        convolution_mode="same",
                                        activation="relu"))
                .layer(SubsamplingLayer(kernel=(2, 2), stride=(2, 2)))
                .layer(ConvolutionLayer(n_out=16, kernel=(3, 3),
                                        convolution_mode="same",
                                        activation="relu"))
                .layer(SubsamplingLayer(kernel=(2, 2), stride=(2, 2)))
                .layer(DenseLayer(n_out=32, activation="relu"))
                .layer(OutputLayer(n_out=nc))
                .set_input_type(InputType.convolutional(12, 12, 1))
                .build())
        net = MultiLayerNetwork(conf).init()
        s = CollectScoresIterationListener()
        net.set_listeners(s)
        net.fit(ArrayDataSetIterator(X, Y, batch_size=32), epochs=20)
        assert s.scores[-1][1] < s.scores[0][1] * 0.7

    def test_batchnorm_in_net(self):
        rng = np.random.default_rng(0)
        X = rng.normal(size=(64, 8, 8, 2)).astype(np.float32)
        Y = np.eye(2, dtype=np.float32)[rng.integers(0, 2, 64)]
        conf = (NeuralNetConfiguration.Builder().seed(1).updater(Sgd(1e-2))
                .list()
                .layer(ConvolutionLayer(n_out=4, kernel=(3, 3),
                                        convolution_mode="same"))
                .layer(BatchNormalization())
                .layer(OutputLayer(n_out=2))
                .set_input_type(InputType.convolutional(8, 8, 2))
                .build())
        net = MultiLayerNetwork(conf).init()
        state_before = np.asarray(net.state["1"]["mean"]).copy()
        net.fit(ArrayDataSetIterator(X, Y, batch_size=32), epochs=2)
        state_after = np.asarray(net.state["1"]["mean"])
        assert not np.allclose(state_before, state_after), \
            "BN running stats must update during fit"


class TestRnnNet:
    def _seq_data(self, n=64, t=6, f=3, nc=2, seed=0):
        rng = np.random.default_rng(seed)
        X = rng.normal(size=(n, t, f)).astype(np.float32)
        labels = (X.sum((1, 2)) > 0).astype(int)
        Y = np.tile(np.eye(nc, dtype=np.float32)[labels][:, None, :], (1, t, 1))
        return X, Y

    def test_lstm_net_trains(self):
        X, Y = self._seq_data()
        conf = (NeuralNetConfiguration.Builder().seed(3).updater(Adam(1e-2))
                .list()
                .layer(LSTM(n_out=8))
                .layer(RnnOutputLayer(n_out=2))
                .set_input_type(InputType.recurrent(3, 6))
                .build())
        net = MultiLayerNetwork(conf).init()
        s = CollectScoresIterationListener()
        net.set_listeners(s)
        net.fit(ArrayDataSetIterator(X, Y, batch_size=32), epochs=15)
        assert s.scores[-1][1] < s.scores[0][1]

    def test_tbptt_matches_epochs(self):
        X, Y = self._seq_data(t=8)
        conf = (NeuralNetConfiguration.Builder().seed(3).updater(Adam(1e-2))
                .list()
                .layer(LSTM(n_out=8))
                .layer(RnnOutputLayer(n_out=2))
                .set_input_type(InputType.recurrent(3, 8))
                .tbptt(4)
                .build())
        net = MultiLayerNetwork(conf).init()
        s = CollectScoresIterationListener()
        net.set_listeners(s)
        net.fit(ArrayDataSetIterator(X, Y, batch_size=32), epochs=5)
        # 2 batches * 2 chunks * 5 epochs = 20 iterations
        assert net.iteration_count == 20
        assert s.scores[-1][1] < s.scores[0][1]

    def test_rnn_time_step_stateful(self):
        X, Y = self._seq_data(n=4, t=6)
        conf = (NeuralNetConfiguration.Builder().seed(3).updater(Adam(1e-2))
                .list()
                .layer(LSTM(n_out=8))
                .layer(RnnOutputLayer(n_out=2))
                .set_input_type(InputType.recurrent(3, 6))
                .build())
        net = MultiLayerNetwork(conf).init()
        full = np.asarray(net.output(X))
        net.rnn_clear_previous_state()
        outs = []
        for t in range(6):
            outs.append(np.asarray(net.rnn_time_step(X[:, t, :])))
        stepped = np.stack(outs, axis=1)
        np.testing.assert_allclose(stepped, full, rtol=1e-4, atol=1e-5)

    def test_masked_training_runs(self):
        X, Y = self._seq_data(t=6)
        mask = np.ones((64, 6), np.float32)
        mask[:, 4:] = 0
        it = ArrayDataSetIterator(X, Y, batch_size=32, features_mask=mask,
                                  labels_mask=mask)
        conf = (NeuralNetConfiguration.Builder().seed(3).updater(Adam(1e-2))
                .list()
                .layer(LSTM(n_out=8))
                .layer(RnnOutputLayer(n_out=2))
                .set_input_type(InputType.recurrent(3, 6))
                .build())
        net = MultiLayerNetwork(conf).init()
        net.fit(it, epochs=2)
        assert np.isfinite(net.score())


class TestSerde:
    def test_conf_json_roundtrip(self):
        conf = mlp_conf(updater=Nesterovs(learning_rate=0.05, momentum=0.8))
        j = conf.to_json()
        back = MultiLayerConfiguration.from_json(j)
        assert back == conf

    def test_model_zip_roundtrip(self, tmp_path):
        X, Y = make_blobs(n=64)
        net = MultiLayerNetwork(mlp_conf()).init()
        net.fit(ArrayDataSetIterator(X, Y, batch_size=32), epochs=3)
        path = str(tmp_path / "model.zip")
        save_model(net, path)
        restored = restore_multilayer_network(path)
        np.testing.assert_allclose(np.asarray(net.output(X[:8])),
                                   np.asarray(restored.output(X[:8])),
                                   rtol=1e-5)
        assert restored.iteration_count == net.iteration_count

    def test_training_resumes_identically(self, tmp_path):
        """Checkpoint must capture updater state: resume == uninterrupted
        (ModelSerializer updaterState.bin semantics)."""
        X, Y = make_blobs(n=64)
        it = lambda: ArrayDataSetIterator(X, Y, batch_size=32)
        netA = MultiLayerNetwork(mlp_conf()).init()
        netA.fit(it(), epochs=2)
        path = str(tmp_path / "ckpt.zip")
        save_model(netA, path)
        netA.fit(it(), epochs=2)

        netB = load_model(path)
        netB.fit(it(), epochs=2)
        np.testing.assert_allclose(np.asarray(netA.params_flat()),
                                   np.asarray(netB.params_flat()),
                                   rtol=1e-4, atol=1e-6)

    def test_frozen_layer_params_do_not_move(self):
        import dataclasses as dc
        X, Y = make_blobs(n=64)
        conf = (NeuralNetConfiguration.Builder().seed(42).updater(Adam(1e-2))
                .list()
                .layer(DenseLayer(n_out=16, activation="relu", frozen=True))
                .layer(OutputLayer(n_out=3))
                .set_input_type(InputType.feed_forward(4))
                .build())
        net = MultiLayerNetwork(conf).init()
        w_before = np.asarray(net.params["0"]["W"]).copy()
        net.fit(ArrayDataSetIterator(X, Y, batch_size=32), epochs=3)
        np.testing.assert_allclose(np.asarray(net.params["0"]["W"]), w_before)
        assert not np.allclose(np.asarray(net.params["1"]["W"]),
                               np.asarray(MultiLayerNetwork(conf).init().params["1"]["W"]))


def test_summary_tables():
    """summary() prints the layer/vertex table (MultiLayerNetwork.java:3230)."""
    from deeplearning4j_tpu.models import LeNet
    from deeplearning4j_tpu.nn.conf.network import GraphBuilder
    from deeplearning4j_tpu.nn.conf.graph_vertices import ElementWiseVertex
    from deeplearning4j_tpu.nn.graph import ComputationGraph
    net = MultiLayerNetwork(LeNet(num_classes=10).conf()).init()
    s = net.summary()
    assert "ConvolutionLayer" in s and "total parameters" in s
    assert f"{net.num_params():,}" in s
    g = (GraphBuilder(NeuralNetConfiguration.Builder().seed(0)
                      .updater(Adam(1e-3)))
         .add_inputs("in")
         .set_input_types(InputType.feed_forward(6)))
    g.add_layer("d", DenseLayer(n_out=6, activation="tanh"), "in")
    g.add_vertex("res", ElementWiseVertex(op="add"), "d", "in")
    g.add_layer("out", OutputLayer(n_out=2), "res")
    g.set_outputs("out")
    gn = ComputationGraph(g.build()).init()
    sg = gn.summary()
    assert "res" in sg and "ElementWiseVertex" in sg
    assert f"{gn.num_params():,}" in sg


class TestScanFit:
    """Input-pipelined fit (scan_steps>1) must be bit-identical to the
    per-call path: same RNG stream, same update math, same listener calls."""

    def test_scan_fit_matches_per_call_bitwise(self):
        X, Y = make_blobs(n=250)        # 250/64 -> ragged tail batch of 58
        a = MultiLayerNetwork(mlp_conf()).init()
        b = MultiLayerNetwork(mlp_conf()).init()
        sa, sb = CollectScoresIterationListener(), CollectScoresIterationListener()
        a.set_listeners(sa)
        b.set_listeners(sb)
        a.fit(ArrayDataSetIterator(X, Y, batch_size=64), epochs=3)
        b.fit(ArrayDataSetIterator(X, Y, batch_size=64), epochs=3,
              scan_steps=3)
        assert a.iteration_count == b.iteration_count
        np.testing.assert_array_equal(
            np.array([s for _, s in sa.scores]),
            np.array([s for _, s in sb.scores]))
        for k in a.params:
            for pk in a.params[k]:
                np.testing.assert_array_equal(
                    np.asarray(a.params[k][pk]), np.asarray(b.params[k][pk]),
                    err_msg=f"{k}/{pk}")

    def test_scan_fit_with_dropout_and_env_default(self, monkeypatch):
        X, Y = make_blobs(n=128)
        conf = (NeuralNetConfiguration.Builder().seed(7).updater(Sgd(1e-2))
                .list()
                .layer(DenseLayer(n_out=16, activation="relu", dropout=0.5))
                .layer(OutputLayer(n_out=3, activation="softmax",
                                   loss="mcxent"))
                .set_input_type(InputType.feed_forward(4)).build())
        a = MultiLayerNetwork(conf).init()
        b = MultiLayerNetwork(conf).init()
        a.fit(ArrayDataSetIterator(X, Y, batch_size=32), epochs=2)
        monkeypatch.setenv("DL4J_TPU_SCAN_STEPS", "4")
        b.fit(ArrayDataSetIterator(X, Y, batch_size=32), epochs=2)
        for k in a.params:
            for pk in a.params[k]:
                np.testing.assert_array_equal(
                    np.asarray(a.params[k][pk]), np.asarray(b.params[k][pk]))

    def test_scan_fit_falls_back_for_model_reading_listeners(self, tmp_path):
        from deeplearning4j_tpu.train.listeners import CheckpointListener
        X, Y = make_blobs(n=128)
        net = MultiLayerNetwork(mlp_conf()).init()
        ckpt = CheckpointListener(str(tmp_path), save_every_n_iterations=2)
        net.set_listeners(ckpt)
        net.fit(ArrayDataSetIterator(X, Y, batch_size=32), epochs=1,
                scan_steps=4)
        # per-call fallback: checkpoints reflect the exact iteration params
        assert len(ckpt._saved) >= 1
        assert net.iteration_count == 3   # 126 samples, drop_last batching


class TestScanStepsDefault:
    def test_cpu_default_is_per_call(self, monkeypatch):
        from deeplearning4j_tpu.nn.fit_loop import _default_scan_steps
        monkeypatch.delenv("DL4J_TPU_SCAN_STEPS", raising=False)
        # conftest pins the cpu backend; per-call is the measured CPU
        # winner (PERF.md: conv-in-scan 10.9x slower on XLA:CPU)
        assert _default_scan_steps() == 1

    def test_env_override_wins(self, monkeypatch):
        from deeplearning4j_tpu.nn.fit_loop import _default_scan_steps
        monkeypatch.setenv("DL4J_TPU_SCAN_STEPS", "7")
        assert _default_scan_steps() == 7

    def test_tpu_default_is_scan10(self, monkeypatch):
        import deeplearning4j_tpu.nn.fit_loop as ml
        monkeypatch.delenv("DL4J_TPU_SCAN_STEPS", raising=False)
        monkeypatch.setattr(ml.jax, "default_backend", lambda: "tpu")
        assert ml._default_scan_steps() == 10

    def test_broken_backend_raises_instead_of_reading_as_cpu(
            self, monkeypatch):
        # a backend that fails to initialise must not quietly switch off
        # scan-of-10, flash and Pallas compile mode
        import deeplearning4j_tpu.util.platform as plat

        def boom():
            raise RuntimeError("Unable to initialize backend 'tpu'")

        monkeypatch.setattr(plat.jax, "default_backend", boom)
        with pytest.raises(RuntimeError, match="initialize backend"):
            plat.is_tpu_backend()

    def test_cpu_is_not_tpu(self):
        import deeplearning4j_tpu.util.platform as plat
        assert plat.is_tpu_backend() is False   # conftest pins cpu


class TestGradientAccumulation:
    def _net(self, seed=21):
        from deeplearning4j_tpu.nn.conf import (
            InputType, NeuralNetConfiguration)
        from deeplearning4j_tpu.nn.layers import DenseLayer, OutputLayer
        from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
        from deeplearning4j_tpu.nn.updaters import Sgd
        conf = (NeuralNetConfiguration.Builder().seed(seed)
                .updater(Sgd(1e-1)).list()
                .layer(DenseLayer(n_out=16, activation="tanh"))
                .layer(OutputLayer(n_out=3, activation="softmax",
                                   loss="mcxent"))
                .set_input_type(InputType.feed_forward(5)).build())
        return MultiLayerNetwork(conf).init()

    def _data(self, n=64):
        rs = np.random.RandomState(3)
        X = rs.randn(n, 5).astype("float32")
        Y = np.eye(3, dtype="float32")[rs.randint(0, 3, n)]
        return X, Y

    def test_accumulation_equals_big_batch(self):
        # 4 micro-batches of 16 accumulated == one step on a batch of 64
        # (equal-size micro means == full-batch mean; BN-free net)
        X, Y = self._data(64)
        a = self._net()
        a.fit((X, Y), batch_size=16, accumulate_steps=4, epochs=2)
        b = self._net()
        b.fit((X, Y), batch_size=64, epochs=2)
        assert a.iteration_count == b.iteration_count == 2
        import jax
        for la, lb in zip(jax.tree_util.tree_leaves(a.params),
                          jax.tree_util.tree_leaves(b.params)):
            np.testing.assert_allclose(np.asarray(la), np.asarray(lb),
                                       rtol=1e-5, atol=1e-6)

    def test_ragged_tail_accumulates_with_correct_mean(self):
        # 6 micro-batches, K=4 -> chunks of 4 and 2 -> 2 optimizer steps,
        # equal to per-call steps on batches of 64 and 32
        X, Y = self._data(96)
        a = self._net()
        a.fit((X, Y), batch_size=16, accumulate_steps=4)
        assert a.iteration_count == 2
        b = self._net()
        b.fit((X[:64], Y[:64]), batch_size=64)
        b.fit((X[64:], Y[64:]), batch_size=32)
        import jax
        for la, lb in zip(jax.tree_util.tree_leaves(a.params),
                          jax.tree_util.tree_leaves(b.params)):
            np.testing.assert_allclose(np.asarray(la), np.asarray(lb),
                                       rtol=1e-5, atol=1e-6)

    def test_conflicting_modes_rejected(self):
        import pytest
        X, Y = self._data(32)
        net = self._net()
        with pytest.raises(ValueError, match="mutually exclusive"):
            net.fit((X, Y), batch_size=16, accumulate_steps=2,
                    scan_steps=2)

    def test_listener_sees_per_step_iterations(self):
        from deeplearning4j_tpu.train.listeners import (
            CollectScoresIterationListener)
        X, Y = self._data(64)
        net = self._net()
        lst = CollectScoresIterationListener()
        net.set_listeners(lst)
        net.fit((X, Y), batch_size=16, accumulate_steps=4, epochs=3)
        assert net.iteration_count == 3           # one step per chunk
        assert len(lst.scores) == 3

    @staticmethod
    def _graph():
        from deeplearning4j_tpu.nn.conf import (
            InputType, NeuralNetConfiguration)
        from deeplearning4j_tpu.nn.conf.network import GraphBuilder
        from deeplearning4j_tpu.nn.graph import ComputationGraph
        from deeplearning4j_tpu.nn.layers import DenseLayer, OutputLayer
        from deeplearning4j_tpu.nn.updaters import Sgd
        g = (GraphBuilder(NeuralNetConfiguration.Builder().seed(9)
                          .updater(Sgd(1e-1)))
             .add_inputs("in")
             .set_input_types(InputType.feed_forward(5)))
        g.add_layer("d", DenseLayer(n_out=16, activation="tanh"), "in")
        g.add_layer("out", OutputLayer(n_out=3, activation="softmax",
                                       loss="mcxent"), "d")
        g.set_outputs("out")
        return ComputationGraph(g.build()).init()

    def test_graph_accumulation_equals_big_batch(self):
        X, Y = self._data(64)
        net = self._graph
        from deeplearning4j_tpu.data.iterator import ArrayDataSetIterator
        a = net()
        a.fit(ArrayDataSetIterator(X, Y, batch_size=16),
              accumulate_steps=4, epochs=2)
        b = net()
        b.fit(ArrayDataSetIterator(X, Y, batch_size=64), epochs=2)
        assert a.iteration_count == b.iteration_count == 2
        import jax
        for la, lb in zip(jax.tree_util.tree_leaves(a.params),
                          jax.tree_util.tree_leaves(b.params)):
            np.testing.assert_allclose(np.asarray(la), np.asarray(lb),
                                       rtol=1e-5, atol=1e-6)

    class GradSpy:
        wants_gradients = True
        reads_model = True

        def __init__(self):
            self.calls = []
            self.trees = []

        def should_capture(self, it):
            return True

        def on_gradients(self, model, it, ep, grads, updates):
            self.calls.append(
                (it, grads is not None and updates is not None))
            self.trees.append((grads, updates))

        def __getattr__(self, name):
            return lambda *a, **k: None

    def test_gradient_listener_gets_averaged_grads(self):
        # wants_gradients listeners receive the AVERAGED per-step grads
        # (lockstep callbacks — no one-chunk deferral on this path)
        X, Y = self._data(64)
        net = self._net()
        spy = self.GradSpy()
        net.set_listeners(spy)
        net.fit((X, Y), batch_size=16, accumulate_steps=4, epochs=2)
        assert spy.calls == [(0, True), (1, True)]

    @pytest.mark.parametrize("how", [
        {"batch_size": 64}, {"batch_size": 16, "accumulate_steps": 4}],
        ids=["per_call", "accumulation"])
    def test_graph_serves_gradient_listeners(self, how):
        # one loop under both containers (nn/fit_loop.py): a graph's
        # per-call and accumulation fits call on_gradients too, with the
        # gradient of the whole effective batch (the mean of the
        # micro-batch gradients) and the update the optimizer made of it
        X, Y = self._data(64)
        net = self._graph()
        want = jax.grad(lambda p: net._score_fn(
            p, net.state, (X,), (Y,), None, None, True, None)[0])(net.params)
        spy = self.GradSpy()
        net.set_listeners(spy)
        net.fit(ArrayDataSetIterator(X, Y, batch_size=how["batch_size"]),
                accumulate_steps=how.get("accumulate_steps", 1), epochs=2)
        assert spy.calls == [(0, True), (1, True)]
        grads, updates = spy.trees[0]
        for g, u, w in zip(*map(jax.tree_util.tree_leaves,
                                (grads, updates, want))):
            np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                       rtol=1e-5, atol=1e-6)
            np.testing.assert_allclose(np.asarray(u), -1e-1 * np.asarray(w),
                                       rtol=1e-5, atol=1e-7)


class TestOneLoop:
    """The fork between the containers stays closed (nn/fit_loop.py)."""

    @pytest.mark.parametrize("module", ["multilayer", "graph"])
    def test_containers_build_no_train_step_and_hold_no_chunk_loop(
            self, module):
        # a container says how a batch becomes operands and how its
        # forward scores them; the gradient, the scan over optimizer
        # steps, the update and the chunk pipeline are fit_loop's.
        # fit_pretrain's layerwise step is not a train step of fit().
        import ast
        import deeplearning4j_tpu.nn as nn_pkg
        path = os.path.join(os.path.dirname(nn_pkg.__file__), module + ".py")
        banned = {"value_and_grad", "grad", "scan", "apply_update",
                  "_run_scan_pipeline", "jit"}
        own = ("fit_pretrain", "output", "rnn_time_step")
        found = []

        def visit(node):
            for child in ast.iter_child_nodes(node):
                if isinstance(child, ast.FunctionDef) and child.name in own:
                    continue
                if isinstance(child, ast.Call):
                    f = child.func
                    name = f.attr if isinstance(f, ast.Attribute) else \
                        getattr(f, "id", None)
                    if name in banned:
                        found.append(f"{module}.py:{child.lineno} {name}")
                visit(child)

        visit(ast.parse(open(path).read()))
        assert not found, found

    def test_one_jit_a_kind_retraces_by_mask_presence(self):
        # the step cache has one entry a (kind, with_stats): jax.jit
        # retraces by pytree structure, so batches with and then without
        # a labels mask are two programs of ONE entry (the parent kept
        # two entries of one program each)
        X, Y = TestGradientAccumulation()._data(32)
        net = TestGradientAccumulation()._net()
        net.fit(DataSet(X, Y, None, np.ones((32,), "float32")), scan_steps=1)
        net.fit(DataSet(X, Y), scan_steps=1)
        assert list(net._steps) == [("step", False)]
        assert net._steps[("step", False)]._cache_size() == 2
