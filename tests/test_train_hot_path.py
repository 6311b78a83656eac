"""Training hot path (PR 2): in-jit gradient accumulation, sync-free fit
loop, lazy Layer write-back, device prefetch in fit, bucketed/overlapped
DP optimizer updates.

The acceptance bar: a steady-state ``Model.fit`` step performs ZERO
synchronous host<->device round trips — every host materialization in the
fit loop funnels through ``hapi.model._host_scalar`` exactly so a counting
hook here can pin it.
"""
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as paddle
import paddle_tpu.nn.functional as F
from paddle_tpu import flags, nn
from paddle_tpu.hapi import Model
from paddle_tpu.hapi import model as hapi_model
from paddle_tpu.jit import TrainStep
from paddle_tpu.optimizer import SGD, Adam, AdamW, Lamb


def _cls_data(n=64, d=8, classes=4, seed=0):
    rng = np.random.default_rng(seed)
    y = rng.integers(0, classes, n)
    means = rng.standard_normal((classes, d)).astype(np.float32) * 2
    x = means[y] + 0.2 * rng.standard_normal((n, d)).astype(np.float32)
    return x, y.astype(np.int64)


def _net(d=8, h=16, classes=4, seed=0):
    paddle.seed(seed)
    return nn.Sequential(nn.Linear(d, h), nn.ReLU(), nn.Linear(h, classes))


class TestGradAccum:
    def test_accum_parity_fp32(self):
        """grad_accum=N matches one full batch bit-for-bit on this fp32
        net (mean-of-grads over equal microbatches == full-batch grad of
        the mean loss)."""
        X, Y = _cls_data()
        n1 = _net()
        s1 = TrainStep(n1, F.cross_entropy,
                       Adam(learning_rate=1e-2, parameters=n1.parameters()),
                       grad_accum=1)
        n2 = _net()
        s2 = TrainStep(n2, F.cross_entropy,
                       Adam(learning_rate=1e-2, parameters=n2.parameters()),
                       grad_accum=4)
        for _ in range(4):
            l1 = float(s1(X, Y).numpy())
            l2 = float(s2(X, Y).numpy())
            assert abs(l1 - l2) < 1e-6, (l1, l2)
        for k in s1._params:
            np.testing.assert_allclose(np.asarray(s1._params[k]),
                                       np.asarray(s2._params[k]),
                                       rtol=2e-6, atol=1e-6)

    def test_accum_composes_with_remat(self):
        X, Y = _cls_data()
        n1 = _net()
        s1 = TrainStep(n1, F.cross_entropy,
                       Adam(learning_rate=1e-2, parameters=n1.parameters()),
                       grad_accum=2)
        n2 = _net()
        s2 = TrainStep(n2, F.cross_entropy,
                       Adam(learning_rate=1e-2, parameters=n2.parameters()),
                       grad_accum=2, remat=True)
        for _ in range(2):
            l1 = float(s1(X, Y).numpy())
            l2 = float(s2(X, Y).numpy())
            # remat recomputes the SAME graph: identical numerics
            assert abs(l1 - l2) < 1e-6, (l1, l2)

    def test_indivisible_batch_raises(self):
        X, Y = _cls_data(n=10)
        net = _net()
        step = TrainStep(net, F.cross_entropy,
                         Adam(learning_rate=1e-2,
                              parameters=net.parameters()),
                         grad_accum=3)
        with pytest.raises(Exception, match="divide"):
            step(X, Y)

    def test_accum_outputs_cover_full_batch_for_metrics(self):
        """return_outputs under accumulation restacks the [accum, Bm, ...]
        scan outputs to the full batch, so fit's train metrics see every
        sample exactly like accum == 1."""
        X, Y = _cls_data(n=16)
        net = _net()
        step = TrainStep(net, F.cross_entropy,
                         Adam(learning_rate=1e-2,
                              parameters=net.parameters()),
                         grad_accum=4, return_outputs=True)
        step(X, Y)
        out = step.last_outputs
        assert out is not None and tuple(out.shape) == (16, 4), out.shape

    def test_env_default_and_trace_key(self, monkeypatch):
        monkeypatch.setenv("PADDLE_TPU_GRAD_ACCUM", "4")
        assert flags.train_grad_accum() == 4
        net = _net()
        step = TrainStep(net, F.cross_entropy,
                         Adam(learning_rate=1e-2,
                              parameters=net.parameters()))
        assert step.grad_accum == 4
        monkeypatch.setenv("PADDLE_TPU_GRAD_ACCUM", "1")
        net2 = _net()
        step2 = TrainStep(net2, F.cross_entropy,
                          Adam(learning_rate=1e-2,
                               parameters=net2.parameters()))
        # the accumulation scan is baked at construction: the key differs
        # so any cache layered on top retraces instead of reusing
        assert step.trace_key != step2.trace_key


class TestAsyncFit:
    def test_async_vs_sync_loss_history_parity(self):
        X, Y = _cls_data()

        def run(async_):
            net = _net()
            m = Model(net)
            m.prepare(Adam(2e-2, parameters=net.parameters()),
                      F.cross_entropy, async_metrics=async_)
            return m.fit((X, Y), batch_size=16, epochs=3, verbose=0,
                         shuffle=True)

        sync = run(False)
        asyn = run(True)
        assert len(sync) == len(asyn)
        for a, b in zip(sync, asyn):
            np.testing.assert_allclose(a["loss"], b["loss"], rtol=1e-5)

    def test_steady_state_fit_step_has_zero_host_syncs(self, monkeypatch):
        """The acceptance hook: count every host materialization in the
        fit loop.  With async metrics, no train metrics, and per-step
        logging off (log_freq=0), a whole epoch drains the device exactly
        ONCE (the stacked epoch-mean fetch) — independent of step count —
        and Tensor.numpy is never called."""
        from paddle_tpu.core.tensor import Tensor

        drains = []
        real = hapi_model._host_scalar
        monkeypatch.setattr(hapi_model, "_host_scalar",
                            lambda x: (drains.append(1), real(x))[1])
        numpys = []
        real_numpy = Tensor.numpy
        monkeypatch.setattr(Tensor, "numpy",
                            lambda self: (numpys.append(1),
                                          real_numpy(self))[1])

        def fit_steps(n_samples):
            drains.clear()
            numpys.clear()
            X, Y = _cls_data(n=n_samples)
            net = _net()
            m = Model(net)
            m.prepare(Adam(2e-2, parameters=net.parameters()),
                      F.cross_entropy, async_metrics=True)
            m.fit((X, Y), batch_size=8, epochs=1, verbose=0, shuffle=False,
                  log_freq=0)
            return len(drains), len(numpys)

        d_small, n_small = fit_steps(32)   # 4 steps
        d_big, n_big = fit_steps(128)      # 16 steps
        assert d_small == d_big == 1, (d_small, d_big)
        assert n_small == n_big == 0, (n_small, n_big)

    def test_log_freq_zero_with_verbose_progbar(self):
        """log_freq=0 (epoch-end-only drain) must not crash the default
        ProgBarLogger (step % 0)."""
        X, Y = _cls_data(n=32)
        net = _net()
        m = Model(net)
        m.prepare(Adam(2e-2, parameters=net.parameters()), F.cross_entropy)
        hist = m.fit((X, Y), batch_size=8, epochs=1, verbose=1, log_freq=0)
        assert np.isfinite(hist[0]["loss"])

    def test_no_metrics_path_builds_no_label_tensor(self, monkeypatch):
        """No metrics registered -> fit must never convert the label to a
        Tensor per step (the old loop built Tensor(np.asarray(y)) each
        batch regardless)."""
        from paddle_tpu.core.tensor import Tensor

        made = []

        class CountingTensor(Tensor):
            def __init__(self, *a, **k):
                made.append(1)
                super().__init__(*a, **k)

        monkeypatch.setattr(hapi_model, "Tensor", CountingTensor)
        X, Y = _cls_data()
        net = _net()
        m = Model(net)
        m.prepare(Adam(2e-2, parameters=net.parameters()), F.cross_entropy)
        m.fit((X, Y), batch_size=16, epochs=1, verbose=0, shuffle=False)
        assert made == [], f"{len(made)} Tensor constructions in fit loop"


class TestLazySync:
    def test_trainstep_lazy_sync_defers_and_syncs(self):
        X, Y = _cls_data()
        net = _net()
        step = TrainStep(net, F.cross_entropy,
                         Adam(learning_rate=1e-2,
                              parameters=net.parameters()),
                         lazy_sync=True)
        step(X, Y)
        assert step._model_stale
        step.sync_to_model()
        assert not step._model_stale
        for k, p in net.named_parameters():
            np.testing.assert_array_equal(np.asarray(p.value),
                                          np.asarray(step._params[k]))

    def test_fit_checkpoint_and_eval_see_synced_params(self, tmp_path):
        X, Y = _cls_data()
        net = _net()
        m = Model(net)
        m.prepare(Adam(2e-2, parameters=net.parameters()), F.cross_entropy)
        m.fit((X, Y), batch_size=16, epochs=2, verbose=0,
              save_dir=str(tmp_path))
        # the checkpoint wrote the FUNCTIONAL (live) params, not a stale
        # snapshot: epoch_1 checkpoint == the step's params at fit end
        from paddle_tpu.framework.io import load as _load

        sd = _load(str(tmp_path / "epoch_1") + ".pdparams")
        for k, p in net.named_parameters():
            np.testing.assert_array_equal(np.asarray(sd[k]),
                                          np.asarray(m._train_step._params[k]))
        # eager eval after fit runs on the synced weights
        logs = m.evaluate((X, Y), batch_size=16, verbose=0)
        assert np.isfinite(logs["eval_loss"])

    def test_mid_fit_eval_syncs(self):
        """eval_data inside fit drains the lazy sync each eval_freq epoch
        (evaluate runs eagerly on the Layer)."""
        X, Y = _cls_data()
        net = _net()
        m = Model(net)
        m.prepare(Adam(2e-2, parameters=net.parameters()), F.cross_entropy)
        hist = m.fit((X, Y), eval_data=(X, Y), batch_size=16, epochs=2,
                     verbose=0)
        assert all("eval_loss" in h and np.isfinite(h["eval_loss"])
                   for h in hist)


class TestFitPrefetch:
    def test_prefetch_ordering_under_shuffle(self):
        """The prefetcher preserves the shuffled batch order exactly: loss
        histories with and without prefetch are identical."""
        X, Y = _cls_data(n=96)

        def run(pf):
            net = _net()
            m = Model(net)
            m.prepare(Adam(2e-2, parameters=net.parameters()),
                      F.cross_entropy)
            return m.fit((X, Y), batch_size=16, epochs=3, verbose=0,
                         shuffle=True, prefetch_factor=pf)

        with_pf = run(4)
        without = run(0)
        for a, b in zip(with_pf, without):
            np.testing.assert_allclose(a["loss"], b["loss"], rtol=1e-6)

    def test_prefetch_env_escape_hatch(self, monkeypatch):
        monkeypatch.setenv("PADDLE_TPU_FIT_PREFETCH", "0")
        assert not flags.fit_prefetch()
        monkeypatch.setenv("PADDLE_TPU_FIT_PREFETCH", "1")
        assert flags.fit_prefetch()
        assert flags.train_step_key()[2] is True

    def test_prefetch_closes_on_early_stop(self):
        """EarlyStopping (stop_training mid-epoch budget) must not leak
        the prefetch thread or wedge fit."""
        from paddle_tpu.hapi import EarlyStopping

        X, Y = _cls_data()
        net = _net()
        m = Model(net)
        m.prepare(Adam(2e-2, parameters=net.parameters()), F.cross_entropy)
        hist = m.fit((X, Y), eval_data=(X, Y), batch_size=16, epochs=20,
                     verbose=0,
                     callbacks=[EarlyStopping(monitor="eval_loss",
                                              patience=1)])
        assert len(hist) <= 20


class TestFitAllThree:
    def test_accum_async_prefetch_fit_matches_sync_loop(self):
        """In-jit accumulation, device-resident losses and prefetch AT
        ONCE, through ``Model.prepare(grad_accum=)`` on a token LM (int
        inputs through an Embedding): after two epochs the parameters
        equal the synchronous ``grad_accum=1`` loop's and every epoch's
        loss is finite.  The tests above turn one of the three on at a
        time."""
        vocab, hidden, T = 64, 32, 16
        toks = np.random.default_rng(0).integers(0, vocab, (24, T + 1))
        X, Y = toks[:, :-1].astype(np.int32), toks[:, 1:].astype(np.int64)

        def run(grad_accum, async_, prefetch):
            paddle.seed(0)
            net = nn.Sequential(nn.Embedding(vocab, hidden),
                                nn.Linear(hidden, hidden), nn.GELU(),
                                nn.LayerNorm(hidden),
                                nn.Linear(hidden, vocab))
            m = Model(net)
            m.prepare(AdamW(learning_rate=1e-3,
                            parameters=net.parameters()),
                      F.cross_entropy, grad_accum=grad_accum,
                      async_metrics=async_)
            hist = m.fit((X, Y), batch_size=8, epochs=2, verbose=0,
                         shuffle=False,
                         prefetch_factor=4 if prefetch else 0)
            return hist, {k: np.asarray(p.value)
                          for k, p in net.named_parameters()}

        _, sync_p = run(1, async_=False, prefetch=False)
        over_hist, over_p = run(2, async_=True, prefetch=True)
        assert len(over_hist) == 2
        assert all(np.isfinite(h["loss"]) for h in over_hist), over_hist
        for k in sync_p:
            np.testing.assert_allclose(over_p[k], sync_p[k], rtol=1e-4,
                                       atol=1e-5, err_msg=k)


class TestBucketedApply:
    def _tree(self, seed=0):
        rng = np.random.default_rng(seed)
        mk = lambda *s: jnp.asarray(rng.standard_normal(s), jnp.float32)
        return {"w1": mk(64, 32), "b1": mk(32), "blk": {"w2": mk(128, 8),
                                                        "s": mk()}}

    def test_bit_exact_vs_plain(self):
        params = self._tree()
        grads = self._tree(seed=1)
        opt = AdamW(learning_rate=1e-2, weight_decay=0.05,
                    apply_decay_param_fun=lambda n: "b1" not in n)
        st = opt.init_state(params)
        p1, s1 = opt.apply_gradients(grads, params, st, lr=1e-2, step=3)
        # tiny bucket_bytes forces several buckets; numerics must not move
        p2, s2 = opt.apply_gradients_bucketed(grads, params, st, lr=1e-2,
                                              step=3, bucket_bytes=2048)
        for a, b in zip(jax.tree_util.tree_leaves(p1),
                        jax.tree_util.tree_leaves(p2)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        for a, b in zip(jax.tree_util.tree_leaves(s1),
                        jax.tree_util.tree_leaves(s2)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    def test_reduce_fn_runs_once_per_bucket(self):
        params = self._tree()
        grads = self._tree(seed=1)
        opt = SGD(learning_rate=0.1)
        st = opt.init_state(params)
        calls = []
        p1, _ = opt.apply_gradients_bucketed(
            grads, params, st, lr=0.1, step=1, bucket_bytes=1 << 30,
            reduce_fn=lambda g: (calls.append(g.shape), g)[1])
        assert len(calls) == 1, calls  # one flat fused "collective"
        p0, _ = opt.apply_gradients(grads, params, st, lr=0.1, step=1)
        for a, b in zip(jax.tree_util.tree_leaves(p0),
                        jax.tree_util.tree_leaves(p1)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    def test_non_elementwise_falls_back(self):
        params = self._tree()
        grads = self._tree(seed=1)
        opt = Lamb(learning_rate=1e-2)  # trust ratio: per-layer norms
        assert not opt._elementwise
        st = opt.init_state(params)
        p1, _ = opt.apply_gradients(grads, params, st, lr=1e-2, step=1)
        p2, _ = opt.apply_gradients_bucketed(grads, params, st, lr=1e-2,
                                             step=1, bucket_bytes=2048)
        for a, b in zip(jax.tree_util.tree_leaves(p1),
                        jax.tree_util.tree_leaves(p2)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    def test_jittable(self):
        params = self._tree()
        grads = self._tree(seed=1)
        opt = AdamW(learning_rate=1e-2)
        st = opt.init_state(params)

        @jax.jit
        def step(g, p, s):
            return opt.apply_gradients_bucketed(g, p, s, lr=1e-2, step=1,
                                                bucket_bytes=2048)

        p2, _ = step(grads, params, st)
        p1, _ = opt.apply_gradients(grads, params, st, lr=1e-2, step=1)
        for a, b in zip(jax.tree_util.tree_leaves(p1),
                        jax.tree_util.tree_leaves(p2)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-6, atol=1e-7)


class TestReducerOverlap:
    def _with_dp_mesh(self, fn):
        from jax.sharding import Mesh

        from paddle_tpu.distributed import env as dist_env

        mesh = Mesh(np.array(jax.devices()[:2]), ("dp",))
        prev = dist_env.get_mesh() if dist_env.has_mesh() else None
        dist_env.set_mesh(mesh)
        try:
            return fn(mesh)
        finally:
            if prev is not None:
                dist_env.set_mesh(prev)

    def test_overlapped_update_matches_plain_step(self):
        from paddle_tpu.distributed.parallel import DataParallel

        class M(nn.Layer):
            def __init__(self):
                super().__init__()
                self.fc_a = nn.Linear(4, 4)
                self.fc_b = nn.Linear(4, 4)

            def forward(self, x):
                return paddle.sum(self.fc_b(self.fc_a(x)) ** 2)

        x = paddle.to_tensor(np.random.default_rng(0).standard_normal(
            (8, 4)).astype(np.float32))

        def run(mesh, overlap):
            paddle.seed(0)
            net = M()
            dp = DataParallel(net, local_grads=True)
            opt = AdamW(learning_rate=0.01, parameters=net.parameters(),
                        weight_decay=0.01)
            flushed = []
            if overlap:
                dp.overlap_optimizer_update(opt)
                inner = dp._reducer._on_flush
                dp._reducer._on_flush = \
                    lambda gi, ps: (flushed.append(gi), inner(gi, ps))[1]
            for _ in range(3):
                loss = dp(x)
                loss.backward()
                dp.sync_gradients()
                opt.step()
                opt.clear_grad()
            dp.close()
            return ({k: np.asarray(p.value)
                     for k, p in net.named_parameters()},
                    flushed, opt._step_count)

        def body(mesh):
            plain, _, n0 = run(mesh, overlap=False)
            over, flushed, n1 = run(mesh, overlap=True)
            assert flushed, "bucket updates never fired"
            # step_group opened each round ONCE: Adam bias correction t
            # advanced identically on both paths
            assert n0 == n1 == 3
            for k in plain:
                np.testing.assert_allclose(plain[k], over[k], rtol=1e-6,
                                           atol=1e-7)

        self._with_dp_mesh(body)

    def test_overlap_raises_on_mid_round_reflush(self):
        """Two backwards between steps re-flush a bucket: with overlapped
        updates the first update already consumed partial grads — must
        fail LOUDLY (the supported accumulation shape is no_sync on the
        non-final backwards)."""
        from paddle_tpu.distributed.parallel import DataParallel

        def body(mesh):
            paddle.seed(0)
            net = nn.Linear(4, 4)
            dp = DataParallel(net, local_grads=True)
            opt = SGD(learning_rate=0.1, parameters=net.parameters())
            dp.overlap_optimizer_update(opt)
            x = paddle.to_tensor(np.ones((4, 4), np.float32))
            paddle.sum(dp(x)).backward()
            with pytest.raises(RuntimeError, match="no_sync"):
                paddle.sum(dp(x)).backward()
            dp.close()

        self._with_dp_mesh(body)

    def test_overlap_accumulation_via_no_sync(self):
        """The documented accumulation shape composes with overlap: quiet
        backwards under no_sync, one flushed backward, one step."""
        from paddle_tpu.distributed.parallel import DataParallel

        def body(mesh):
            paddle.seed(0)
            net = nn.Linear(4, 4)
            dp = DataParallel(net, local_grads=True)
            opt = SGD(learning_rate=0.1, parameters=net.parameters())
            dp.overlap_optimizer_update(opt)
            x = paddle.to_tensor(np.ones((4, 4), np.float32))
            with dp.no_sync():
                paddle.sum(dp(x)).backward()
            paddle.sum(dp(x)).backward()
            dp.sync_gradients()
            opt.step()
            opt.clear_grad()
            assert opt._step_count == 1
            dp.close()

        self._with_dp_mesh(body)

    def test_overlap_respects_optimizer_ownership(self):
        """Reducer buckets cover the whole model; an optimizer owning only
        a subset must never update the rest via step_group (same rule as
        step())."""
        from paddle_tpu.distributed.parallel import DataParallel

        class M(nn.Layer):
            def __init__(self):
                super().__init__()
                self.backbone = nn.Linear(4, 4)
                self.head = nn.Linear(4, 4)

            def forward(self, x):
                return paddle.sum(self.head(self.backbone(x)) ** 2)

        def body(mesh):
            paddle.seed(0)
            net = M()
            before = {k: np.asarray(p.value)
                      for k, p in net.backbone.named_parameters()}
            dp = DataParallel(net, local_grads=True)
            opt = SGD(learning_rate=0.1,
                      parameters=net.head.parameters())
            dp.overlap_optimizer_update(opt)
            x = paddle.to_tensor(np.ones((4, 4), np.float32))
            paddle.sum(dp(x)).backward()
            dp.sync_gradients()
            opt.step()
            dp.close()
            for k, p in net.backbone.named_parameters():
                np.testing.assert_array_equal(np.asarray(p.value),
                                              before[k])
            assert any(
                not np.array_equal(np.asarray(p.value), 0 * np.asarray(
                    p.value)) for p in net.head.parameters())

        self._with_dp_mesh(body)

    def test_overlap_rejects_global_clip(self):
        from paddle_tpu.distributed.parallel import DataParallel
        from paddle_tpu.nn import ClipGradByGlobalNorm

        def body(mesh):
            net = nn.Linear(4, 4)
            dp = DataParallel(net, local_grads=True)
            opt = SGD(learning_rate=0.1, parameters=net.parameters(),
                      grad_clip=ClipGradByGlobalNorm(1.0))
            with pytest.raises(ValueError, match="grad_clip"):
                dp.overlap_optimizer_update(opt)
            dp.close()

        self._with_dp_mesh(body)


class TestShardedTrainStepBucketed:
    def test_dp_bucketed_matches_single_device(self):
        """The fleet DP step's bucketed fused update changes scheduling,
        never numerics: dp=2 training equals the dp=1 run."""
        from jax.sharding import Mesh, PartitionSpec as P

        from paddle_tpu.distributed.fleet.base import ShardedTrainStep

        rng = np.random.default_rng(0)
        # numpy leaves: the step donates its device buffers, so each run
        # must device_put its own fresh copies
        w0 = rng.standard_normal((8, 4)).astype(np.float32)
        b0 = np.zeros((4,), np.float32)
        X = jnp.asarray(rng.standard_normal((8, 8)), jnp.float32)
        Y = jnp.asarray(rng.standard_normal((8, 4)), jnp.float32)

        def loss_fn(p, batch, key):
            x, y = batch
            return jnp.mean((x @ p["w"] + p["b"] - y) ** 2)

        def run(ndev):
            mesh = Mesh(np.array(jax.devices()[:ndev]), ("dp",))
            step = ShardedTrainStep(
                loss_fn, {"w": w0.copy(), "b": b0.copy()},
                AdamW(learning_rate=1e-2), mesh=mesh,
                batch_spec=P("dp") if ndev > 1 else P())
            for _ in range(3):
                loss = step((X, Y))
            return jax.device_get(step.params), float(loss.numpy())

        p1, l1 = run(1)
        p2, l2 = run(2)
        assert abs(l1 - l2) < 1e-6
        for k in p1:
            np.testing.assert_allclose(p1[k], p2[k], rtol=1e-6, atol=1e-7)


class TestTrainFlags:
    def test_async_train_env(self, monkeypatch):
        monkeypatch.setenv("PADDLE_TPU_ASYNC_TRAIN", "0")
        assert not flags.async_train()
        net = _net()
        step = TrainStep(net, F.cross_entropy,
                         Adam(learning_rate=1e-2,
                              parameters=net.parameters()))
        assert not step.async_metrics
        monkeypatch.delenv("PADDLE_TPU_ASYNC_TRAIN")
        assert flags.async_train()

    def test_train_step_key_folds_all_flags(self, monkeypatch):
        monkeypatch.setenv("PADDLE_TPU_GRAD_ACCUM", "2")
        monkeypatch.setenv("PADDLE_TPU_ASYNC_TRAIN", "0")
        monkeypatch.setenv("PADDLE_TPU_FIT_PREFETCH", "0")
        k1 = flags.train_step_key()
        monkeypatch.setenv("PADDLE_TPU_GRAD_ACCUM", "8")
        k2 = flags.train_step_key()
        monkeypatch.setenv("PADDLE_TPU_ASYNC_TRAIN", "1")
        k3 = flags.train_step_key()
        monkeypatch.setenv("PADDLE_TPU_FIT_PREFETCH", "1")
        k4 = flags.train_step_key()
        assert len({k1, k2, k3, k4}) == 4
