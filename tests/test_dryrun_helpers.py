"""Unit tests for the dry-run analysis helpers (pure functions — no
device-count forcing needed): HLO collective parsing, spec sanitizing,
model-FLOPs accounting, input specs."""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import PartitionSpec as P


# import via module path without triggering the XLA_FLAGS side effect?
# dryrun sets XLA_FLAGS at import — harmless here because jax is already
# initialized with 1 device in the test process (flag is ignored after
# first init), and the helpers under test are pure.
from repro.launch import dryrun as dr
from repro.configs import SHAPES, get_config


def test_collective_stats_parses_ops():
    hlo = """
  %ag = bf16[16,1024]{1,0} all-gather(%x), replica_groups={}
  %ar = f32[512]{0} all-reduce(%y), to_apply=%add
  %rs = (bf16[8,64]{1,0}, bf16[8,64]{1,0}) reduce-scatter(%a, %b)
  %aa = s32[4,4]{1,0} all-to-all(%c)
  %cp = bf16[2,2]{1,0} collective-permute(%d)
  %ags = bf16[32]{0} all-gather-start(%e)
  %dot = f32[8,8]{1,0} dot(%p, %q)
"""
    st = dr.collective_stats(hlo)
    assert st["num_collectives"] == 6
    kinds = st["bytes_by_kind"]
    assert kinds["all-gather"] == 16 * 1024 * 2 + 32 * 2
    assert kinds["all-reduce"] == 512 * 4
    assert kinds["reduce-scatter"] == 2 * 8 * 64 * 2
    assert kinds["all-to-all"] == 16 * 4
    assert kinds["collective-permute"] == 4 * 2
    assert st["total_bytes"] == sum(kinds.values())


def test_collective_stats_ignores_non_collectives():
    st = dr.collective_stats("%dot = f32[128,128]{1,0} dot(%a, %b)")
    assert st["num_collectives"] == 0
    assert st["total_bytes"] == 0


def test_sanitize_drops_indivisible_axes():
    mesh = jax.make_mesh((1, 1), ("data", "model"))
    # pretend a 16-wide model axis via a fake mesh is hard on 1 device;
    # test the divisibility logic directly with the 1x1 mesh (every dim
    # divides 1, so specs pass through)
    sds = jax.ShapeDtypeStruct((51865, 64), jnp.float32)
    spec = P("model", None)
    out = dr._sanitize(spec, sds, mesh)
    assert out == spec


def test_sanitize_mixed_tree():
    mesh = jax.make_mesh((1, 1), ("data", "model"))
    tree = {"a": P("data", None), "b": P(("data", "model"), None)}
    sds = {"a": jax.ShapeDtypeStruct((4, 2), jnp.float32),
           "b": jax.ShapeDtypeStruct((8, 2), jnp.float32)}
    out = dr._sanitize(tree, sds, mesh)
    assert out["a"] == P("data", None)


def test_model_flops_modes():
    cfg = get_config("phi3-medium-14b")
    n = cfg.active_param_count()
    tr = dr.model_flops(cfg, SHAPES["train_4k"])
    pf = dr.model_flops(cfg, SHAPES["prefill_32k"])
    dc = dr.model_flops(cfg, SHAPES["decode_32k"])
    assert tr == 6.0 * n * 256 * 4096
    assert pf == 2.0 * n * 32 * 32768
    assert dc == 2.0 * n * 128


def test_model_flops_moe_uses_active():
    kimi = get_config("kimi-k2-1t-a32b")
    tr = dr.model_flops(kimi, SHAPES["train_4k"])
    assert tr < 6.0 * kimi.param_count() * 256 * 4096 / 10  # 1T total


def test_input_specs_shapes():
    cfg = get_config("whisper-small")
    sp = dr.input_specs(cfg, SHAPES["train_4k"])
    assert sp["tokens"].shape == (256, 4096)
    assert sp["audio"].shape == (256, cfg.encoder_seq_len, cfg.d_model)
    sp_d = dr.input_specs(cfg, SHAPES["decode_32k"])
    assert sp_d["tokens"].shape == (128,)
    vlm = get_config("llama-3.2-vision-90b")
    sp_v = dr.input_specs(vlm, SHAPES["prefill_32k"])
    assert sp_v["vision"].shape == (32, vlm.vision_tokens, vlm.vision_dim)


@pytest.mark.parametrize("from_env", [True, False])
def test_compile_cache_placement(from_env, monkeypatch, tmp_path):
    """Entry points keep the compile cache where JAX_COMPILATION_CACHE_DIR
    says and set nothing else; without it, in `.jax_cache/` at the
    checkout root, which .gitignore lists."""
    from repro.launch.compile_cache import CHECKOUT_ROOT, use_compile_cache
    before = jax.config.jax_compilation_cache_dir
    try:
        if from_env:
            monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
            assert use_compile_cache() == str(tmp_path)
            assert jax.config.jax_compilation_cache_dir == before
        else:
            monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
            path = use_compile_cache()
            assert path == str(CHECKOUT_ROOT / ".jax_cache")
            assert jax.config.jax_compilation_cache_dir == path
            assert (CHECKOUT_ROOT / "src" / "repro").is_dir()
            ignored = (CHECKOUT_ROOT / ".gitignore").read_text().split()
            assert ".jax_cache/" in ignored
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
