"""Sharding rules + synthetic data pipeline invariants."""
import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import AbstractMesh, PartitionSpec as P

from repro import configs as CFG
from repro.data.synthetic import SyntheticConfig, config_for, make_batch
from repro.launch import specs as SP
from repro.sharding.rules import (
    ShardingRules,
    batch_pspec,
    cache_pspecs,
    param_pspecs,
)


def _mesh11():
    return jax.make_mesh((1, 1), ("data", "model"))


def test_param_specs_no_duplicate_axes():
    """No PartitionSpec may reuse a mesh axis (the jamba MoE regression)."""
    mesh = _mesh11()
    rules = ShardingRules(batch=("data",), fsdp=("data",))
    for arch in CFG.ARCH_IDS:
        cfg = CFG.get_config(arch)
        pshapes = SP.params_shapes(cfg)
        specs = param_pspecs(pshapes, mesh, rules)
        for spec in jax.tree.leaves(specs,
                                    is_leaf=lambda x: isinstance(x, P)):
            axes = []
            for entry in spec:
                if entry is None:
                    continue
                axes += list(entry) if isinstance(entry, tuple) else [entry]
            assert len(axes) == len(set(axes)), (arch, spec)


def test_divisibility_fallback_replicates():
    """Indivisible dims must fall back to replication (abstract 16x16
    production mesh — rule logic only needs mesh.shape)."""
    mesh = AbstractMesh((16, 16), ("data", "model"))
    rules = ShardingRules()
    cfg = CFG.get_config("llava-next-34b")       # 56 q heads x 128
    pshapes = SP.params_shapes(cfg)
    specs = param_pspecs(pshapes, mesh, rules)
    wq = specs["blocks"]["sub0"]["mixer"]["wq"]
    assert wq[-1] == "model"                      # 7168 % 16 == 0
    assert wq[-2] in ("data", ("data",))          # fsdp dim
    # danube: head_dim 80 -> H*hd = 2560 divisible; kv 8*80=640 divisible
    cfg2 = CFG.get_config("h2o-danube-1.8b")
    specs2 = param_pspecs(SP.params_shapes(cfg2), mesh, rules)
    assert specs2["blocks"]["sub0"]["mixer"]["wk"][-1] == "model"
    # a 6-expert hypothetical would replicate: simulate via small moe cfg
    from repro.models.config import ModelConfig, uniform_pattern
    cfg3 = ModelConfig(name="x", num_layers=1, d_model=64, num_heads=4,
                       num_kv_heads=4, head_dim=16, d_ff=96, vocab_size=160,
                       pattern=uniform_pattern(moe=True), num_experts=6,
                       num_experts_per_tok=2)
    specs3 = param_pspecs(SP.params_shapes(cfg3), mesh, rules)
    gate = specs3["blocks"]["sub0"]["mlp"]["gate"]
    assert gate[1] is None                        # 6 % 16 != 0 -> replicate


def test_cache_specs_shapes_and_validity():
    mesh = _mesh11()
    rules = ShardingRules(kv_seq=("data", "model"))
    cfg = CFG.get_config("jamba-1.5-large-398b")
    shapes = jax.eval_shape(
        lambda: __import__("repro.models.model",
                           fromlist=["init_cache"]).init_cache(cfg, 1, 512))
    specs = cache_pspecs(cfg, mesh, rules, 1, shapes)
    for spec in jax.tree.leaves(specs, is_leaf=lambda x: isinstance(x, P)):
        jax.sharding.NamedSharding(mesh, spec)   # must not raise


def test_batch_pspec_divisibility():
    mesh = jax.make_mesh((1, 1), ("data", "model"))
    rules = ShardingRules()
    # batch=1 cannot shard over data -> replicated lead
    assert batch_pspec(mesh, rules, 2, 1)[0] is None


def test_synthetic_determinism_and_structure():
    scfg = SyntheticConfig(batch=4, seq_len=32, vocab_size=101)
    a = make_batch(scfg, 7)
    b = make_batch(scfg, 7)
    np.testing.assert_array_equal(np.asarray(a["tokens"]),
                                  np.asarray(b["tokens"]))
    c = make_batch(scfg, 8)
    assert not np.array_equal(np.asarray(a["tokens"]),
                              np.asarray(c["tokens"]))
    # targets are next-token shifted
    full_a = np.asarray(a["tokens"])
    full_t = np.asarray(a["targets"])
    np.testing.assert_array_equal(full_a[:, 1:], full_t[:, :-1])
    assert full_a.min() >= 0 and full_a.max() < 101


def test_synthetic_vision_and_codebooks():
    cfg = CFG.get_smoke_config("llava-next-34b")
    scfg = config_for(cfg, 2, 16)
    b = make_batch(scfg, 0)
    assert b["vision_embeds"].shape == (2, cfg.vision_tokens, cfg.d_model)
    cfgm = CFG.get_smoke_config("musicgen-large")
    bm = make_batch(config_for(cfgm, 2, 16), 0)
    assert bm["tokens"].shape == (2, 16, 4)


def test_input_specs_match_assigned_shapes():
    for arch in CFG.ARCH_IDS:
        cfg = CFG.get_config(arch)
        tr = SP.train_inputs(cfg, CFG.SHAPES["train_4k"])
        s_text = 4096 - (cfg.vision_tokens or 0)
        assert tr["tokens"].shape[0] == 256
        assert tr["tokens"].shape[1] == s_text
        dec = SP.decode_inputs(cfg, CFG.SHAPES["decode_32k"])
        assert dec["tokens_new"].shape[0] == 128
        assert dec["position"].shape == (128,)
        # cache buffers bounded by the shape's seq (ring-buffer for SWA)
        for leaf in jax.tree.leaves(dec["caches"]):
            if leaf.ndim == 5:
                assert leaf.shape[2] <= 32768
