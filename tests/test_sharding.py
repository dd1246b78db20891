"""Sharding rules: divisibility-aware resolution, strategy semantics, and
that every assigned arch's param tree resolves on the production mesh
shape (checked structurally — no devices needed)."""
import jax
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.configs import ARCHS
from repro.models import transformer as T
from repro.models.modules import ParamSpec
from repro.sharding import strategy as S


class FakeMesh:
    """Duck-typed mesh: only .shape and .axis_names are consulted."""
    def __init__(self, shape_dict):
        self.shape = shape_dict
        self.axis_names = tuple(shape_dict)


SINGLE = FakeMesh({"data": 16, "model": 16})
MULTI = FakeMesh({"pod": 2, "data": 16, "model": 16})


def test_ddp_replicates_everything():
    rules = S.rules_for("ddp", SINGLE)
    spec = ParamSpec((1024, 512), ("embed", "mlp"))
    assert S.spec_to_pspec(spec, rules, SINGLE) == P(None, None)


def test_zero3_shards_embed_over_data_and_mlp_over_model():
    rules = S.rules_for("zero3", SINGLE)
    spec = ParamSpec((1024, 512), ("embed", "mlp"))
    assert S.spec_to_pspec(spec, rules, SINGLE) == P("data", "model")


def test_indivisible_axis_falls_back_to_replication():
    rules = S.rules_for("zero3", SINGLE)
    # vocab 50280 is not divisible by 16 -> replicated
    spec = ParamSpec((50280, 1024), ("vocab", "embed"))
    ps = S.spec_to_pspec(spec, rules, SINGLE)
    assert ps == P(None, "data")


def test_no_mesh_axis_used_twice_per_tensor():
    rules = S.rules_for("tp", SINGLE)
    spec = ParamSpec((256, 256), ("heads", "mlp"))  # both want "model"
    ps = S.spec_to_pspec(spec, rules, SINGLE)
    assert ps == P("model", None)


def test_inference_layout_expert_parallel():
    rules = S.rules_for("tp", SINGLE)
    spec = ParamSpec((16, 5120, 8192), ("experts", "embed", "mlp"))
    ps = S.spec_to_pspec(spec, rules, SINGLE)
    assert ps == P("data", None, "model")


def test_multipod_zero3_embed_over_pod_and_data():
    rules = S.rules_for("zero3", MULTI)
    spec = ParamSpec((4096, 12288), ("embed", "mlp"))
    ps = S.spec_to_pspec(spec, rules, MULTI)
    assert ps == P(("pod", "data"), "model")


def test_zero1_params_replicated_but_opt_sharded():
    prules = S.rules_for("zero1", SINGLE)
    orules = S.opt_rules_for("zero1", SINGLE)
    spec = ParamSpec((1024, 512), ("embed", "mlp"))
    assert S.spec_to_pspec(spec, prules, SINGLE) == P(None, None)
    assert S.spec_to_pspec(spec, orules, SINGLE) == P("data", "model")


@pytest.mark.parametrize("arch", list(ARCHS))
@pytest.mark.parametrize("strategy", ["ddp", "zero3", "tp"])
def test_all_arch_param_trees_resolve(arch, strategy):
    cfg = ARCHS[arch]
    for mesh in (SINGLE, MULTI):
        pspecs = S.param_pspecs(cfg, mesh, strategy)
        specs = T.param_specs(cfg)

        def check(sp, ps):
            assert len(ps) <= len(sp.shape)
            used = [a for a in jax.tree_util.tree_leaves(tuple(ps))
                    if a is not None]
            # divisibility of every sharded dim
            for dim, axis in zip(sp.shape, tuple(ps) + (None,) * 8):
                if axis is None:
                    continue
                axes = (axis,) if isinstance(axis, str) else axis
                size = int(np.prod([mesh.shape[a] for a in axes]))
                assert dim % size == 0, (arch, sp.shape, ps)

        jax.tree_util.tree_map(
            check, specs, pspecs,
            is_leaf=lambda x: isinstance(x, ParamSpec))


def test_batch_pspec():
    assert S.batch_pspec(SINGLE, 256, 2) == P("data", None)
    assert S.batch_pspec(SINGLE, 1, 2) == P(None, None)
    assert S.batch_pspec(MULTI, 256, 3) == P(("pod", "data"), None, None)
    # batch divisible by data but not pod*data
    assert S.batch_pspec(MULTI, 16, 2) == P("data", None)


# ------------------------------------------------------------------- #
# edge rules: fused-QKV / GQA shapes, ZeRO-1 composition, cache pspecs
# ------------------------------------------------------------------- #
def test_fused_qkv_indivisible_head_dim_replicates():
    """A fused QKV projection (H*hd + 2*KV*hd columns) whose fused dim
    does not divide the model axis must degrade to replication, not
    crash or mis-shard."""
    rules = S.rules_for("tp", SINGLE)
    D, H, KV, hd = 512, 7, 2, 24                  # (7 + 4) * 24 = 264
    fused = ParamSpec((D, (H + 2 * KV) * hd), ("embed", "heads"))
    assert (H + 2 * KV) * hd % 16 != 0
    assert S.spec_to_pspec(fused, rules, SINGLE) == P(None, None)
    # divisible fused dim shards: (14 + 2) * 64 = 1024 = 16 * 64
    fused_ok = ParamSpec((D, 16 * 64), ("embed", "heads"))
    assert S.spec_to_pspec(fused_ok, rules, SINGLE) == P(None, "model")


def test_gqa_kv_heads_smaller_than_model_axis():
    """GQA KV projections whose kv_heads*hd dim is smaller than the
    16-way model axis stay replicated while the Q projection shards."""
    rules = S.rules_for("tp", SINGLE)
    wk = ParamSpec((512, 2 * 4), ("embed", "kv_heads"))    # 8 rows < 16
    assert S.spec_to_pspec(wk, rules, SINGLE) == P(None, None)
    wq = ParamSpec((512, 16 * 4), ("embed", "heads"))
    assert S.spec_to_pspec(wq, rules, SINGLE) == P(None, "model")


def test_zero1_opt_rules_compose_with_tp():
    """ZeRO-1 over an arbitrary param strategy: moments inherit the param
    layout plus `embed` over the data axes; params stay put."""
    prules = S.rules_for("tp", SINGLE)
    orules = S.zero1_opt_rules("tp", SINGLE)
    spec = ParamSpec((1024, 512), ("embed", "mlp"))
    assert S.spec_to_pspec(spec, prules, SINGLE) == P(None, "model")
    assert S.spec_to_pspec(spec, orules, SINGLE) == P("data", "model")
    # ddp params + zero1 moments: moments shard over data only
    assert S.spec_to_pspec(spec, S.zero1_opt_rules("ddp", SINGLE),
                           SINGLE) == P("data", None)
    # zero3 already shards embed over data; zero1 composition is a no-op
    assert (S.zero1_opt_rules("zero3", SINGLE)
            == S.rules_for("zero3", SINGLE))
    # multi-pod: embed shards over BOTH data axes
    assert S.spec_to_pspec(spec, S.zero1_opt_rules("ddp", MULTI),
                           MULTI) == P(("pod", "data"), None)


def test_train_state_pspecs_structure():
    """The TrainState pspec tree mirrors (params, AdamState(m, v, step),
    step) with zero=1 moments data-sharded and scalars replicated."""
    from repro.models.config import ModelConfig
    cfg = ModelConfig(name="t", arch_type="dense", n_layers=2, d_model=64,
                      n_heads=4, n_kv_heads=2, d_ff=128, vocab_size=64,
                      compute_dtype="float32", remat=False)
    ts = S.train_state_pspecs(cfg, SINGLE, "tp", zero=1)
    assert ts.step == P()
    assert ts.opt.step == P()
    # params and moments have the same tree structure
    pt = jax.tree_util.tree_structure(ts.params)
    assert jax.tree_util.tree_structure(ts.opt.m) == pt
    assert jax.tree_util.tree_structure(ts.opt.v) == pt
    # at least one moment leaf gained a data axis its param lacks
    flat_p = jax.tree_util.tree_leaves(ts.params)
    flat_m = jax.tree_util.tree_leaves(ts.opt.m)

    def uses_data(ps):
        return any("data" in ((a,) if isinstance(a, str) else tuple(a))
                   for a in ps if a is not None)

    assert any(uses_data(m) and not uses_data(p)
               for p, m in zip(flat_p, flat_m))


def test_cache_pspecs_batch_axis():
    """KV cache layout: batch shards over data when divisible (else
    replicates), the KV length axis (after the KV heads) shards over
    model when divisible."""
    import jax as _jax
    from repro.models.config import ModelConfig
    from repro.models import transformer as T

    cfg = ModelConfig(name="t", arch_type="dense", n_layers=2, d_model=64,
                      n_heads=4, n_kv_heads=2, d_ff=128, vocab_size=64,
                      compute_dtype="float32", remat=False)
    # batch 32 % data(16) == 0, S=64 % model(16) == 0 -> both shard
    ps = S.cache_pspecs(T.cache_struct(cfg, 32, 64), SINGLE, 32)
    for leaf in _jax.tree_util.tree_leaves(
            ps, is_leaf=lambda x: isinstance(x, P)):
        assert leaf[1] == "data"
        assert leaf[3] == "model"
    # indivisible batch replicates rows; odd S replicates the length
    ps = S.cache_pspecs(T.cache_struct(cfg, 3, 65), SINGLE, 3)
    for leaf in _jax.tree_util.tree_leaves(
            ps, is_leaf=lambda x: isinstance(x, P)):
        assert leaf[1] is None
        assert leaf[3] is None
