"""Strategy compiler: toggle validation/ordering + model routing
(reference MetaOptimizerFactory meta_optimizer_factory.py:27 +
StrategyCompiler strategy_compiler.py:114)."""
import numpy as np
import pytest

import jax
from jax.sharding import Mesh

import paddle_tpu as paddle
from paddle_tpu import nn
from paddle_tpu.distributed import init_parallel_env
from paddle_tpu.distributed.fleet import compile_strategy
from paddle_tpu.distributed.fleet.strategy import DistributedStrategy
from paddle_tpu.distributed.fleet.strategy_compiler import (
    build_layer_train_step)
from paddle_tpu.distributed.pp_layers import LayerDesc, PipelineLayer
from paddle_tpu.framework.errors import InvalidArgumentError
from paddle_tpu.optimizer import Adam


class TestCompile:
    def test_ordering(self):
        s = DistributedStrategy()
        s.amp = True
        s.sharding = True
        s.recompute = True
        plan = compile_strategy(s, {"dp": 8})
        assert plan.rules == ("amp", "recompute", "sharding")
        assert plan.zero_stage == 1

    def test_conflicts_raise(self):
        s = DistributedStrategy()
        s.dgc = True
        s.localsgd = True
        with pytest.raises(InvalidArgumentError, match="cannot compose"):
            compile_strategy(s, {"dp": 8})
        s2 = DistributedStrategy()
        s2.lamb = True
        s2.lars = True
        with pytest.raises(InvalidArgumentError, match="cannot compose"):
            compile_strategy(s2, {"dp": 8})

    def test_missing_axis_raises(self):
        s = DistributedStrategy()
        s.pipeline = True
        with pytest.raises(InvalidArgumentError, match="mesh axis 'pp'"):
            compile_strategy(s, {"dp": 8})

    def test_zero_stage_and_n_micro_resolved(self):
        s = DistributedStrategy()
        s.sharding = True
        s.sharding_configs = {"stage": 3}
        s.pipeline = True
        s.pipeline_configs = {"accumulate_steps": 4}
        plan = compile_strategy(s, {"dp": 2, "pp": 2})
        assert plan.zero_stage == 3 and plan.n_micro == 4


class TestRouting:
    def _mesh(self, shape, names):
        devs = np.array(jax.devices()[: int(np.prod(shape))]).reshape(shape)
        return Mesh(devs, names)

    def test_pipeline_routes_to_pipeline_layer(self):
        init_parallel_env({"pp": 2})
        s = DistributedStrategy()
        s.pipeline = True
        s.pipeline_configs = {"accumulate_steps": 2}
        pl = PipelineLayer([LayerDesc(nn.Linear, 8, 16),
                            LayerDesc(nn.ReLU),
                            LayerDesc(nn.Linear, 16, 4)], num_stages=2)
        pl.train()
        rng = np.random.default_rng(0)
        X = rng.standard_normal((8, 8)).astype(np.float32)
        Y = rng.integers(0, 4, 8).astype(np.int64)
        step = build_layer_train_step(pl, nn.functional.cross_entropy,
                                      Adam(learning_rate=1e-2), s,
                                      mesh=self._mesh((2,), ("pp",)),
                                      example_input=X)
        losses = [float(step(X, Y).value) for _ in range(5)]
        assert np.isfinite(losses).all() and losses[-1] < losses[0]

    def test_pipeline_needs_pipeline_layer(self):
        s = DistributedStrategy()
        s.pipeline = True
        with pytest.raises(InvalidArgumentError, match="PipelineLayer"):
            build_layer_train_step(nn.Linear(4, 4), None, None, s,
                                   mesh=self._mesh((2,), ("pp",)))

    def test_layer_route_rejects_unsupported_toggles(self):
        from paddle_tpu.framework.errors import UnimplementedError

        s = DistributedStrategy()
        s.sharding = True
        net = nn.Linear(4, 4)
        with pytest.raises(UnimplementedError, match="functional"):
            build_layer_train_step(net, nn.functional.cross_entropy,
                                   Adam(learning_rate=1e-2,
                                        parameters=net.parameters()), s,
                                   mesh=self._mesh((1,), ("dp",)))

    def test_degraded_mesh_disables_axis_toggles(self):
        """allow_degrade dev loop: axis-requiring toggles disable with a
        warning instead of raising (reference _disable_strategy)."""
        import jax.numpy as jnp

        from paddle_tpu.distributed.fleet import Fleet

        s = DistributedStrategy()
        s.tensor_parallel = True
        s.hybrid_configs = {"mp_degree": 64}  # more than visible devices
        with pytest.warns(UserWarning, match="degrading mesh"):
            f = Fleet().init(strategy=s, allow_degrade=True)
        params = {"w": np.ones((4, 2), np.float32)}

        def loss_fn(p, batch, key):
            return jnp.mean((batch @ p["w"]) ** 2)

        with pytest.warns(UserWarning, match="disabled"):
            step = f.build_train_step(loss_fn, params,
                                      Adam(learning_rate=1e-3))
        out = step(np.ones((8, 4), np.float32))
        assert np.isfinite(float(out.value))

    def test_plain_routes_to_train_step(self):
        from paddle_tpu.jit import TrainStep

        s = DistributedStrategy()
        s.recompute = True
        net = nn.Linear(4, 4)
        step = build_layer_train_step(net, nn.functional.cross_entropy,
                                      Adam(learning_rate=1e-2,
                                           parameters=net.parameters()), s,
                                      mesh=self._mesh((1,), ("dp",)))
        assert isinstance(step, TrainStep)


def test_recompute_policy_flows_from_strategy():
    """RecomputeConfig.policy selects the checkpoint policy of the
    sharded step; every alias resolves and an invalid one is loud."""
    import numpy as np

    import paddle_tpu as paddle
    from paddle_tpu.distributed.fleet.strategy import DistributedStrategy
    from paddle_tpu.ops.remat_policies import resolve

    import jax

    assert resolve("full") is None
    assert resolve("nothing_saveable") is None
    # "dots" is checkpoint_dots joined with flash attention's two named
    # residuals (tests/test_gpt_hybrid.py::TestRematPolicies counts them)
    dots = resolve("dots_saveable")
    assert dots(jax.lax.dot_general_p) and not dots(jax.lax.tanh_p)
    assert resolve("everything_saveable") \
        is jax.checkpoint_policies.everything_saveable
    try:
        resolve("bogus")
        raise AssertionError("no raise")
    except ValueError:
        pass

    # end-to-end: a sharded step with recompute + dots policy still trains
    from paddle_tpu.distributed.fleet.base import ShardedTrainStep

    rng = np.random.default_rng(0)
    W = paddle.to_tensor(rng.standard_normal((4, 4)).astype(np.float32))

    def loss_fn(params, batch, key):
        x, y = batch
        pred = x @ params["w"]
        return ((pred - y) ** 2).mean()

    strat = DistributedStrategy()
    strat.recompute = True
    strat.recompute_configs.policy = "dots_saveable"
    opt = paddle.optimizer.SGD(learning_rate=0.1)
    step = ShardedTrainStep(loss_fn, {"w": W.value}, opt, strategy=strat)
    import jax.numpy as jnp
    x = jnp.asarray(rng.standard_normal((8, 4)).astype(np.float32))
    y = jnp.asarray(rng.standard_normal((8, 4)).astype(np.float32))
    l0 = float(step((x, y)))
    for _ in range(10):
        l1 = float(step((x, y)))
    assert l1 < l0
