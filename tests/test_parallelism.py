"""ShardingPlan rule tests (run on 1 device with an abstract 16x16 mesh via
AbstractMesh — no devices needed for spec computation)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import AbstractMesh, PartitionSpec as P

from repro.core import parallelism as par


def mesh_single():
    return AbstractMesh((16, 16), ("data", "model"))


def mesh_multi():
    return AbstractMesh((2, 16, 16), ("pod", "data", "model"))


class Leaf:
    def __init__(self, shape):
        self.shape = shape


class TestPlanAxes:
    def test_dp_uses_all_axes_for_batch(self):
        plan = par.make_plan("dp", mesh_single())
        assert set(plan.batch_axes) == {"data", "model"}
        assert plan.tensor_axes == ()

    def test_dp_tp_hybrid(self):
        plan = par.make_plan("dp_tp", mesh_multi())
        assert plan.batch_axes == ("pod", "data")
        assert plan.tensor_axes == ("model",)

    def test_tp_pure(self):
        plan = par.make_plan("tp", mesh_single())
        assert plan.batch_axes == ()
        assert set(plan.tensor_axes) == {"data", "model"}

    def test_unknown_plan_raises(self):
        with pytest.raises(ValueError):
            par.make_plan("nope", mesh_single())


class TestParamRules:
    def setup_method(self):
        self.plan = par.make_plan("dp_tp", mesh_single())

    def test_embed_vocab_sharded(self):
        spec = self.plan.spec_for_param("embed/table", (262144, 3840))
        assert spec == P(("model",), None)

    def test_attention_heads_sharded(self):
        spec = self.plan.spec_for_param("blocks/l0/attn/wq", (8, 3840, 4096))
        assert spec == P(None, None, ("model",))
        spec = self.plan.spec_for_param("blocks/l0/attn/wo", (8, 4096, 3840))
        assert spec == P(None, ("model",), None)

    def test_indivisible_dim_replicated(self):
        # kv dim 8·80=640 ÷ 16 = 40 OK; but 8 heads*hd=120 not ÷16 → replicate
        spec = self.plan.spec_for_param("blocks/l0/attn/wk", (4, 256, 120))
        assert spec == P(None, None, None)

    def test_moe_expert_dim_sharded_when_divisible(self):
        # qwen3: 128 experts ÷ 16 → expert-parallel
        spec = self.plan.spec_for_param("blocks/l0/moe/w_in", (48, 128, 2048, 768))
        assert spec == P(None, ("model",), None, None)
        # mixtral: 8 experts not ÷ 16 → shard d_ff instead
        spec = self.plan.spec_for_param("blocks/l0/moe/w_in", (32, 8, 4096, 14336))
        assert spec == P(None, None, None, ("model",))

    def test_norms_replicated(self):
        assert self.plan.spec_for_param("blocks/l0/ln1/scale", (4, 3840)) == P()


class TestZeRO1:
    def test_opt_state_gains_data_axis(self):
        plan = par.make_plan("dp_tp_zero1", mesh_single())
        params = {"blocks": {"l0": {"mlp": {"w_in": Leaf((8, 4096, 16384))}}}}
        specs = plan.opt_specs(params)
        s = specs["blocks"]["l0"]["mlp"]["w_in"]
        flat = [a for a in s if a is not None]
        assert ("model",) in s or "model" in str(s)
        assert "data" in str(s)     # the ZeRO upgrade

    def test_baseline_opt_state_matches_params(self):
        plan = par.make_plan("dp_tp", mesh_single())
        params = {"w": Leaf((8, 4096, 16384))}
        assert plan.opt_specs(params) == plan.param_specs(params)


class TestBatchAndCache:
    def test_batch_sharded_over_pod_data(self):
        plan = par.make_plan("dp_tp", mesh_multi())
        spec = plan.spec_for_batch_leaf("tokens", (256, 4096))
        assert spec == P(("pod", "data"), None)

    def test_batch_of_one_replicated(self):
        plan = par.make_plan("dp_tp", mesh_single())
        assert plan.spec_for_batch_leaf("tokens", (1, 524288)) == P(None, None)

    def test_cache_seq_sharded_when_batch_unshardable(self):
        plan = par.make_plan("dp_tp_seq", mesh_single())
        spec = plan.spec_for_cache_leaf("blocks/l0/k", (8, 1, 524288, 8, 256))
        assert spec[2] in ("data", ("data",))

    def test_cache_kv_heads_sharded_when_divisible(self):
        plan = par.make_plan("dp_tp", mesh_single())
        spec = plan.spec_for_cache_leaf("blocks/l0/k", (32, 128, 32768, 32, 80))
        assert spec[1] in ("data", ("data",))
        assert spec[3] in ("model", ("model",))


class TestConstrainContext:
    def test_noop_without_context(self):
        x = jnp.ones((4, 8))
        y = par.constrain(x, ("batch", None))
        assert y is x
