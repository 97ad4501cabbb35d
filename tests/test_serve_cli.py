"""The serving entry point's set-up helpers (launch/serve.py): where JAX's
persistent compilation cache lives, the device label every report carries,
and the engine the CLI flags build."""
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest
from jax.experimental.compilation_cache import compilation_cache

from repro.launch import serve
from repro.serving.batch_engine import BatchedSpeculativeEngine, ShardedBatchedSpeculativeEngine
from repro.serving.engine import SpeculativeEngine

CACHE_KEYS = ("jax_compilation_cache_dir", "jax_enable_compilation_cache",
              "jax_persistent_cache_min_compile_time_secs")


@pytest.fixture
def restore_cache_config():
    saved = {k: getattr(jax.config, k) for k in CACHE_KEYS}
    yield
    for k, v in saved.items():
        jax.config.update(k, v)
    compilation_cache.reset_cache()


def test_compile_cache_env_dir_wins_and_holds_entries(monkeypatch, tmp_path,
                                                      restore_cache_config):
    """With JAX_COMPILATION_CACHE_DIR set, compiled entries land there."""
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert serve.setup_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == str(tmp_path)
    jax.config.update("jax_enable_compilation_cache", True)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    compilation_cache.reset_cache()
    jax.jit(lambda x: x * 3 + 1)(jnp.arange(7.0)).block_until_ready()
    assert any(p.name.endswith("-cache") for p in tmp_path.iterdir())


def test_compile_cache_default_is_one_ignored_checkout_dir(monkeypatch,
                                                           restore_cache_config):
    """Without the variable the cache is one fixed directory at the root of
    the checkout, listed in .gitignore: the same path in every process."""
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    root = Path(serve.__file__).resolve().parents[3]
    path = serve.setup_compile_cache()
    assert path == serve.setup_compile_cache() == str(root / ".jax_cache")
    assert ".jax_cache/" in (root / ".gitignore").read_text().split()


def test_device_label_names_the_backend():
    assert serve.device_label() == f"cpu:{jax.devices()[0].device_kind} x{len(jax.devices())}"


def test_build_engine_follows_flags():
    args = serve.build_parser().parse_args(
        ["--arch", "granite-3-2b", "--smoke", "--streams", "2", "--attention-impl", "pallas"])
    cfg, tp, dcfg, dp = serve.build_models(args)
    assert cfg.attention_impl == dcfg.attention_impl == "pallas"
    eng = serve.build_engine(args, cfg, tp, dcfg, dp)
    assert isinstance(eng, BatchedSpeculativeEngine)
    assert eng.n_slots == 2 and eng.paged and eng.pipeline
    args = serve.build_parser().parse_args(["--arch", "granite-3-2b", "--smoke", "--seed", "5"])
    single = serve.build_engine(args, cfg, tp, dcfg, dp)
    assert isinstance(single, SpeculativeEngine) and single.ecfg.seed == 5
    args = serve.build_parser().parse_args(
        ["--arch", "granite-3-2b", "--smoke", "--streams", "2", "--data-shards", "2"])
    sharded = serve.build_engine(args, cfg, tp, dcfg, dp, devices=jax.devices()[:1])
    assert isinstance(sharded, ShardedBatchedSpeculativeEngine)
    assert [p["target_pool"] for p in sharded.placement()] == [[0], [0]]
