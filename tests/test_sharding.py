"""Sharding-rule unit tests: divisibility fallbacks, path rules, cache specs.

These run against an *abstract* 16x16 / 2x16x16 mesh built on CPU only for
spec computation (AbstractMesh — no devices needed), so they validate the
rules without the 512-device override."""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import AbstractMesh, PartitionSpec as P

from repro.distributed import sharding as shd
from repro.models.registry import get_config, get_model, input_specs
from repro.configs.base import SHAPES

MESH = AbstractMesh((16, 16), ("data", "model"))
MESH3 = AbstractMesh((2, 16, 16), ("pod", "data", "model"))


class TestGreedySpec:
    def test_divisible_takes_first_candidate(self):
        spec = shd.greedy_spec((128, 4096), [["data"], ["model"]], MESH)
        assert spec == P("data", "model")

    def test_indivisible_falls_through(self):
        # 40 experts don't divide model=16 -> replicated; ff 512 does
        spec = shd.greedy_spec((40, 1536, 512),
                               [["model"], ["data"], ["model"]], MESH)
        assert spec == P(None, "data", "model")

    def test_axis_used_once(self):
        spec = shd.greedy_spec((64, 64), [["model"], ["model"]], MESH)
        assert spec == P("model")  # second dim replicated, trailing None dropped

    def test_composite_batch_axis(self):
        spec = shd.greedy_spec((256, 4096), [[("pod", "data"), "data"], []], MESH3)
        assert spec == P(("pod", "data"))

    def test_composite_falls_back_to_single(self):
        # batch 16 not divisible by 32 -> falls to data(16)
        spec = shd.greedy_spec((16, 4096), [[("pod", "data"), "data"], []], MESH3)
        assert spec == P("data")

    def test_priority_order(self):
        # both dims want model; priority gives it to dim 2 (kv heads)
        spec = shd.greedy_spec((8, 32768, 16, 128),
                               [[], ["model"], ["model"], []], MESH,
                               priority=[0, 2, 1, 3])
        assert spec == P(None, None, "model")


class TestParamRules:
    def test_all_archs_all_params_get_valid_specs(self):
        for arch in ("qwen2-7b", "granite-moe-3b-a800m", "deepseek-v2-lite-16b",
                     "zamba2-1.2b", "xlstm-125m", "hubert-xlarge", "gemma-7b"):
            cfg = get_config(arch)
            model = get_model(cfg)
            specs = jax.eval_shape(model.init, jax.random.PRNGKey(0))

            def check(path, leaf):
                p = shd.param_spec(shd._path_str(path), leaf.shape, MESH)
                # every named axis must divide its dim
                flat = []
                for i, entry in enumerate(p):
                    if entry is None:
                        continue
                    axes = entry if isinstance(entry, tuple) else (entry,)
                    prod = 1
                    for a in axes:
                        prod *= MESH.shape[a]
                    assert leaf.shape[i] % prod == 0, (arch, shd._path_str(path),
                                                       leaf.shape, p)

            jax.tree_util.tree_map_with_path(check, specs)

    def test_embedding_vocab_sharded(self):
        spec = shd.param_spec("embed/table", (152064, 3584), MESH)
        assert spec == P("model", "data")

    def test_granite_odd_vocab_replicates_vocab_dim(self):
        spec = shd.param_spec("embed/table", (49155, 1536), MESH)
        assert spec[0] is None  # 49155 = 3*5*29*113: nothing divides

    def test_moe_expert_parallel_when_divisible(self):
        # deepseek: 64 experts / model=16 OK
        spec = shd.param_spec("blocks/moe/gate", (27, 64, 2048, 1408), MESH)
        assert spec == P(None, "model", "data")

    def test_moe_tensor_parallel_fallback(self):
        # granite: 40 experts don't divide -> ff TP
        spec = shd.param_spec("blocks/moe/gate", (32, 40, 1536, 512), MESH)
        assert spec == P(None, None, "data", "model")

    def test_stacked_layer_dim_never_sharded(self):
        spec = shd.param_spec("blocks/attn/wq/w", (28, 3584, 3584), MESH)
        assert spec[0] is None


class TestCacheRules:
    def test_gqa_cache_heads_sharded_when_divisible(self):
        cfg = get_config("gemma-7b")
        model = get_model(cfg)
        cache = model.cache_spec(128, 32768)
        sh = shd.cache_shardings(cache, MESH)
        assert sh["layers"]["k"].spec == P(None, "data", None, "model")

    def test_qwen2_7b_kv4_falls_to_sequence(self):
        cfg = get_config("qwen2-7b")
        model = get_model(cfg)
        cache = model.cache_spec(128, 32768)
        sh = shd.cache_shardings(cache, MESH)
        # 4 kv heads don't divide model=16 -> sequence-sharded cache
        assert sh["layers"]["k"].spec == P(None, "data", "model")

    def test_long_context_batch1_uses_model_on_heads(self):
        cfg = get_config("zamba2-1.2b")
        model = get_model(cfg)
        cache = model.cache_spec(1, 524288)
        sh = shd.cache_shardings(cache, MESH)
        assert sh["attn"]["k"].spec == P(None, None, None, "model")

    def test_offset_replicated(self):
        cfg = get_config("qwen2-0.5b")
        model = get_model(cfg)
        sh = shd.cache_shardings(model.cache_spec(8, 128), MESH)
        assert sh["offset"].spec == P()


class TestInputRules:
    @pytest.mark.parametrize("shape_name", list(SHAPES))
    def test_inputs_shard_batch(self, shape_name):
        cfg = get_config("qwen2-0.5b")
        shape = SHAPES[shape_name]
        specs = input_specs(cfg, shape)
        sh = shd.input_shardings(specs, MESH3)
        for leaf, s in zip(jax.tree.leaves(specs), jax.tree.leaves(sh)):
            if s.spec and s.spec[0]:
                axes = s.spec[0] if isinstance(s.spec[0], tuple) else (s.spec[0],)
                prod = 1
                for a in axes:
                    prod *= MESH3.shape[a]
                assert leaf.shape[0] % prod == 0
