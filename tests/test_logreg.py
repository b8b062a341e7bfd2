"""Tier-3 E2E tests for the LogisticRegression app.

Counterparts of the reference's app-as-test usage (SURVEY.md §4.2: LR MNIST
example run). Synthetic linearly-separable data; the invariant is high test
accuracy + decreasing loss for every objective/mode combination.
"""

import os

import numpy as np
import pytest

from multiverso_tpu.models.logreg.configure import Configure
from multiverso_tpu.models.logreg.logreg import LogReg


def _write_dense(path, X, y):
    with open(path, "w") as f:
        for row, lab in zip(X, y):
            f.write(f"{lab} " + " ".join(f"{v:.5f}" for v in row) + "\n")


def _write_sparse(path, X, y, weighted=False):
    with open(path, "w") as f:
        for row, lab in zip(X, y):
            nz = np.nonzero(row)[0]
            head = f"{lab}:1.0" if weighted else f"{lab}"
            f.write(head + " " + " ".join(f"{k}:{row[k]:.5f}" for k in nz) + "\n")


@pytest.fixture(scope="module")
def dense_binary(tmp_path_factory):
    rng = np.random.default_rng(0)
    d = tmp_path_factory.mktemp("lr_dense")
    w_true = rng.normal(size=8)
    X = rng.normal(size=(600, 8)).astype(np.float32)
    y = (X @ w_true > 0).astype(int)
    _write_dense(d / "train.data", X[:500], y[:500])
    _write_dense(d / "test.data", X[500:], y[500:])
    return d


@pytest.fixture(scope="module")
def sparse_binary(tmp_path_factory):
    rng = np.random.default_rng(1)
    d = tmp_path_factory.mktemp("lr_sparse")
    dim = 50
    w_true = rng.normal(size=dim)
    X = rng.normal(size=(600, dim)).astype(np.float32)
    X[rng.random(X.shape) < 0.7] = 0  # sparsify
    y = (X @ w_true > 0).astype(int)
    _write_sparse(d / "train.data", X[:500], y[:500])
    _write_sparse(d / "test.data", X[500:], y[500:])
    return d


def _config(d, **kw):
    cfg = Configure()
    cfg.train_file = str(d / "train.data")
    cfg.test_file = str(d / "test.data")
    cfg.output_model_file = str(d / "model.bin")
    cfg.output_file = str(d / "test.out")
    cfg.show_time_per_sample = 10 ** 9
    for k, v in kw.items():
        setattr(cfg, k, v)
    return cfg


class TestLocalDense:
    def test_sigmoid_learns(self, dense_binary):
        cfg = _config(dense_binary, input_size=8, output_size=1,
                      objective_type="sigmoid", updater_type="sgd",
                      learning_rate=0.5, train_epoch=5)
        lr = LogReg(cfg)
        lr.Train()
        acc = lr.Test()
        assert acc > 0.9
        assert os.path.exists(cfg.output_model_file)
        assert os.path.exists(cfg.output_file)

    def test_bfloat16_compute_tracks_float32(self, dense_binary):
        """compute_type=bfloat16 (mixed precision) must learn like f32:
        same data, both reach high accuracy and nearby weights."""
        weights = {}
        for ct in ("float32", "bfloat16"):
            cfg = _config(dense_binary, input_size=8, output_size=1,
                          objective_type="sigmoid", updater_type="sgd",
                          learning_rate=0.5, train_epoch=5)
            cfg.compute_type = ct
            lr = LogReg(cfg)
            lr.Train()
            assert lr.Test() > 0.9
            weights[ct] = lr.model.weights().copy()
        np.testing.assert_allclose(weights["bfloat16"], weights["float32"],
                                   rtol=0.15, atol=0.05)

    def test_softmax_multiclass(self, tmp_path):
        rng = np.random.default_rng(2)
        centers = np.array([[2, 0, 0], [0, 2, 0], [0, 0, 2]], np.float32)
        X = np.vstack([rng.normal(c, 0.4, size=(150, 3)) for c in centers])
        y = np.repeat([0, 1, 2], 150)
        perm = rng.permutation(len(X))
        X, y = X[perm].astype(np.float32), y[perm]
        _write_dense(tmp_path / "train.data", X[:380], y[:380])
        _write_dense(tmp_path / "test.data", X[380:], y[380:])
        cfg = _config(tmp_path, input_size=3, output_size=3,
                      objective_type="softmax", updater_type="sgd",
                      learning_rate=0.5, train_epoch=6, regular_type="L2")
        lr = LogReg(cfg)
        lr.Train()
        assert lr.Test() > 0.9

    def test_model_store_load_roundtrip(self, dense_binary):
        cfg = _config(dense_binary, input_size=8, output_size=1,
                      objective_type="sigmoid", updater_type="sgd",
                      learning_rate=0.5, train_epoch=3)
        lr = LogReg(cfg)
        lr.Train()
        acc1 = lr.Test()
        cfg2 = _config(dense_binary, input_size=8, output_size=1,
                       objective_type="sigmoid",
                       init_model_file=cfg.output_model_file)
        lr2 = LogReg(cfg2)
        acc2 = lr2.Test()
        assert abs(acc1 - acc2) < 1e-9


class TestLocalSparse:
    def test_sparse_sigmoid(self, sparse_binary):
        cfg = _config(sparse_binary, input_size=50, output_size=1,
                      sparse=True, objective_type="sigmoid",
                      updater_type="sgd", learning_rate=0.5, train_epoch=5)
        lr = LogReg(cfg)
        lr.Train()
        assert lr.Test() > 0.85

    def test_ftrl(self, sparse_binary):
        cfg = _config(sparse_binary, input_size=50, output_size=1,
                      objective_type="ftrl", alpha=1.0, beta=1.0,
                      lambda1=0.01, lambda2=0.01, train_epoch=8)
        lr = LogReg(cfg)
        lr.Train()
        assert lr.Test() > 0.85

    def test_weight_reader(self, tmp_path):
        rng = np.random.default_rng(3)
        w_true = rng.normal(size=10)
        X = rng.normal(size=(200, 10)).astype(np.float32)
        y = (X @ w_true > 0).astype(int)
        _write_sparse(tmp_path / "train.data", X, y, weighted=True)
        cfg = _config(tmp_path, input_size=10, output_size=1, sparse=True,
                      reader_type="weight", objective_type="sigmoid",
                      updater_type="sgd", train_epoch=3)
        cfg.test_file = ""
        lr = LogReg(cfg)
        loss = lr.Train()
        assert loss < 0.3


class TestPSModes:
    def test_ps_dense(self, dense_binary):
        cfg = _config(dense_binary, input_size=8, output_size=1,
                      use_ps=True, objective_type="sigmoid",
                      updater_type="sgd", learning_rate=0.5, train_epoch=5,
                      sync_frequency=1, pipeline=False)
        lr = LogReg(cfg)
        lr.Train()
        acc = lr.Test()
        lr.close()
        assert acc > 0.9

    def test_ps_dense_pipelined(self, dense_binary):
        cfg = _config(dense_binary, input_size=8, output_size=1,
                      use_ps=True, objective_type="sigmoid",
                      updater_type="sgd", learning_rate=0.5, train_epoch=5,
                      sync_frequency=2, pipeline=True)
        lr = LogReg(cfg)
        lr.Train()
        acc = lr.Test()
        lr.close()
        assert acc > 0.85

    def test_ps_sparse(self, sparse_binary):
        cfg = _config(sparse_binary, input_size=50, output_size=1,
                      use_ps=True, sparse=True, objective_type="sigmoid",
                      updater_type="sgd", learning_rate=0.5, train_epoch=5)
        lr = LogReg(cfg)
        lr.Train()
        acc = lr.Test()
        lr.close()
        assert acc > 0.85

    def test_ps_sparse_compressed_identical_loss(self, sparse_binary):
        """compress="sparse" on the PS table is EXACT (index/value pairs
        or the dense fallback, both lossless): the training run must be
        bit-for-bit the run without compression. LR's row pushes are
        dense WITHIN the touched rows (the row protocol is already
        sparsity-aware), so the >50%-zeros rule correctly falls back —
        the filter engages on workloads with intra-row zeros
        (TestWireCompression asserts the byte reduction there)."""
        results = {}
        for mode in ("", "sparse"):
            cfg = _config(sparse_binary, input_size=50, output_size=1,
                          use_ps=True, sparse=True,
                          objective_type="sigmoid", updater_type="sgd",
                          learning_rate=0.5, train_epoch=5, compress=mode)
            lr = LogReg(cfg)
            loss = lr.Train()
            acc = lr.Test()
            lr.close()
            results[mode] = (loss, acc)
        assert results["sparse"][0] == results[""][0], results
        assert results["sparse"][1] == results[""][1] > 0.85, results

    def test_ps_sparse_1bit_trains(self, sparse_binary):
        """compress="1bit" is lossy; error feedback must still take the
        model to a usable accuracy."""
        cfg = _config(sparse_binary, input_size=50, output_size=1,
                      use_ps=True, sparse=True, objective_type="sigmoid",
                      updater_type="sgd", learning_rate=0.5, train_epoch=8,
                      compress="1bit")
        lr = LogReg(cfg)
        lr.Train()
        acc = lr.Test()
        lr.close()
        assert acc > 0.8, acc

    def test_ps_ftrl(self, sparse_binary):
        cfg = _config(sparse_binary, input_size=50, output_size=1,
                      use_ps=True, objective_type="ftrl", alpha=1.0,
                      beta=1.0, lambda1=0.01, lambda2=0.01, train_epoch=8)
        lr = LogReg(cfg)
        lr.Train()
        acc = lr.Test()
        lr.close()
        assert acc > 0.85


class TestConfigFile:
    def test_reference_style_config(self, dense_binary, tmp_path):
        cfg_text = f"""# mnist-style config (reference example/mnist.config keys)
input_size=8
output_size=1
objective_type=sigmoid
regular_type=L2
updater_type=sgd
train_epoch=4
sparse=false
use_ps=false
minibatch_size=20
train_file={dense_binary}/train.data
test_file={dense_binary}/test.data
output_file={tmp_path}/test.out
output_model_file={tmp_path}/model.bin
learning_rate_coef=7e6
regular_coef=0.0007
"""
        path = tmp_path / "run.config"
        path.write_text(cfg_text)
        cfg = Configure.from_file(str(path))
        assert cfg.input_size == 8 and cfg.regular_type == "L2"
        lr = LogReg(cfg)
        lr.Train()
        assert lr.Test() > 0.85


class TestLifecycle:
    def test_init_failure_does_not_strand_zoo(self, dense_binary):
        """A raise during PS-mode construction (after the lazy MV_Init) must
        bring the owned world down with the exception — a stranded Zoo
        poisons every later MV_Init in the process (the round-3 suite-order
        leak class, now guarded by utils.world.WorldOwner)."""
        from multiverso_tpu.zoo import Zoo
        # output_size=0 -> the PS ArrayTable gets size 0 and its CHECK
        # raises inside Model.Get, strictly after the lazy MV_Init
        cfg = _config(dense_binary, input_size=8, output_size=0,
                      use_ps=True)
        with pytest.raises(Exception):
            LogReg(cfg)
        assert not Zoo.Get().started
        # and a fresh PS world must come up cleanly afterwards
        lr = LogReg(_config(dense_binary, input_size=8, output_size=1,
                            use_ps=True, train_epoch=1))
        try:
            lr.Train()
        finally:
            lr.close()
        assert not Zoo.Get().started


class TestDevicePlane:
    """device_plane=true: whole windows train as one jit'd program over
    the PS tables' HBM storage; must match the host plane exactly (same
    verb order — window-start cache, summed linear deltas)."""

    def test_staging_budget_never_invents_a_chip(self, monkeypatch):
        """A quarter of what the device reports; only the CPU backend,
        which reports nothing, gets the 1GB default — a chip whose
        memory_stats() fails is an error, not a 1GB chip."""
        import jax
        from multiverso_tpu.models.logreg.device_plane import (
            DeviceWindowTrainer)

        class Dev:
            def __init__(self, stats):
                self.stats = stats

            def memory_stats(self):
                if isinstance(self.stats, Exception):
                    raise self.stats
                return self.stats

        budget = DeviceWindowTrainer._device_staging_budget
        assert budget() == 1 << 30                      # this CPU backend
        monkeypatch.setattr(jax, "local_devices",
                            lambda: [Dev({"bytes_limit": 16 << 30})])
        assert budget() == 4 << 30
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        monkeypatch.setattr(jax, "local_devices", lambda: [Dev(None)])
        with pytest.raises(TypeError):
            budget()
        monkeypatch.setattr(jax, "local_devices",
                            lambda: [Dev(RuntimeError("chip lost"))])
        with pytest.raises(RuntimeError, match="chip lost"):
            budget()

    def _final_weights(self, d, **kw):
        kw.setdefault("objective_type", "sigmoid")
        cfg = _config(d, use_ps=True, updater_type="sgd",
                      learning_rate=0.5, train_epoch=4, pipeline=False,
                      **kw)
        lr = LogReg(cfg)
        try:
            lr.Train()
            return lr.model.weights().copy(), lr.Test()
        finally:
            lr.close()

    def test_dense_matches_host_plane(self, dense_binary):
        # sync_frequency divides the 25 batches/epoch: the host plane's
        # modulo-counter sync then lands exactly on window boundaries,
        # where the device plane's per-window refresh is bit-comparable
        W_h, acc_h = self._final_weights(dense_binary, input_size=8,
                                         output_size=1, sync_frequency=5)
        W_d, acc_d = self._final_weights(dense_binary, input_size=8,
                                         output_size=1, sync_frequency=5,
                                         device_plane=True)
        np.testing.assert_allclose(W_d, W_h, rtol=1e-4, atol=1e-6)
        assert acc_d > 0.9 and abs(acc_d - acc_h) < 0.02

    def test_sparse_matches_host_plane(self, sparse_binary):
        W_h, acc_h = self._final_weights(sparse_binary, input_size=50,
                                         output_size=1, sparse=True,
                                         sync_frequency=5)
        W_d, acc_d = self._final_weights(sparse_binary, input_size=50,
                                         output_size=1, sparse=True,
                                         sync_frequency=5,
                                         device_plane=True)
        np.testing.assert_allclose(W_d, W_h, rtol=1e-4, atol=1e-6)
        assert acc_d > 0.85 and abs(acc_d - acc_h) < 0.02

    def test_softmax_multiclass_device(self, tmp_path):
        rng = np.random.default_rng(3)
        W_true = rng.normal(size=(8, 3))
        X = rng.normal(size=(600, 8)).astype(np.float32)
        y = np.argmax(X @ W_true, axis=1)
        _write_dense(tmp_path / "train.data", X[:500], y[:500])
        _write_dense(tmp_path / "test.data", X[500:], y[500:])
        _, acc = self._final_weights(tmp_path, input_size=8, output_size=3,
                                     objective_type="softmax",
                                     sync_frequency=2, device_plane=True)
        assert acc > 0.85

    def test_ftrl_matches_host_plane(self, sparse_binary):
        """FTRL device plane (round 5): the two-table (z, n) KV window
        program must track the host KV-verb path — same window-start
        state convention, same negated-accumulator pushes."""
        W_h, acc_h = self._final_weights(sparse_binary, input_size=50,
                                         output_size=1, sparse=True,
                                         objective_type="ftrl",
                                         alpha=1.0, beta=1.0,
                                         lambda1=0.01, lambda2=0.01,
                                         sync_frequency=5)
        W_d, acc_d = self._final_weights(sparse_binary, input_size=50,
                                         output_size=1, sparse=True,
                                         objective_type="ftrl",
                                         alpha=1.0, beta=1.0,
                                         lambda1=0.01, lambda2=0.01,
                                         sync_frequency=5,
                                         device_plane=True)
        np.testing.assert_allclose(W_d, W_h, rtol=1e-4, atol=1e-6)
        assert acc_d > 0.8 and abs(acc_d - acc_h) < 0.02


class TestReaderFastPaths:
    def test_epoch_cache_matches_streaming(self, dense_binary):
        """cache_data replays the IDENTICAL window sequence: final weights
        must be bit-equal to re-parsing every epoch."""
        weights = {}
        for cached in (True, False):
            cfg = _config(dense_binary, input_size=8, output_size=1,
                          objective_type="sigmoid", updater_type="sgd",
                          learning_rate=0.5, train_epoch=3,
                          cache_data=cached)
            lr = LogReg(cfg)
            lr.Train()
            weights[cached] = lr.model.weights().copy()
        np.testing.assert_array_equal(weights[True], weights[False])

    def test_dense_fast_parser_matches_parse_line(self, tmp_path):
        from multiverso_tpu.models.logreg.data import (
            _iter_samples_dense_fast, parse_line)
        rng = np.random.default_rng(9)
        X = rng.normal(size=(57, 5)).astype(np.float32)
        y = rng.integers(0, 2, 57)
        _write_dense(tmp_path / "d.data", X, y)
        cfg = _config(tmp_path, input_size=5, output_size=1)
        fast = list(_iter_samples_dense_fast(str(tmp_path / "d.data"), cfg))
        slow = [parse_line(l, 5, False, False)
                for l in open(tmp_path / "d.data")]
        assert len(fast) == len(slow) == 57
        for (fl, fw, _, fv), (sl, sw, _, sv) in zip(fast, slow):
            assert fl == sl and fw == sw
            np.testing.assert_array_equal(fv, sv)

    def test_dense_fast_parser_rejects_bad_width(self, tmp_path):
        from multiverso_tpu.utils.log import FatalError
        from multiverso_tpu.models.logreg.data import (
            _iter_samples_dense_fast)
        (tmp_path / "bad.data").write_text("1 0.5 0.5\n0 0.1 0.2 0.3\n")
        cfg = _config(tmp_path, input_size=3, output_size=1)
        with pytest.raises(FatalError):
            list(_iter_samples_dense_fast(str(tmp_path / "bad.data"), cfg))

    def test_dense_fast_parser_rejects_coincidental_reshape(self, tmp_path):
        """Ragged widths whose token TOTAL still divides evenly must not
        silently misparse (np.loadtxt validates per-line columns)."""
        from multiverso_tpu.utils.log import FatalError
        from multiverso_tpu.models.logreg.data import (
            _iter_samples_dense_fast)
        # widths 2 and 4: total 6 == 2 lines * 3 cols would reshape
        (tmp_path / "c.data").write_text("1 0.5\n0 0.1 0.2 0.3\n")
        cfg = _config(tmp_path, input_size=2, output_size=1)
        with pytest.raises(FatalError):
            list(_iter_samples_dense_fast(str(tmp_path / "c.data"), cfg))
