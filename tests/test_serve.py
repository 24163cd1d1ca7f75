"""The serve entry point on a registry config, the engine-vs-reference
logit check that ``chip_smoke.py`` makes on the chip, and the persistent
compilation cache setup — all at the smoke widths on the CPU."""
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import smoke
from repro.data import tasks
from repro.launch import serve as srv
from repro.models import Model

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))
import chip_smoke  # noqa: E402  (a script at the repo root)


def test_serve_registry_config_completes_cleanly():
    """``serve(model_cfg=...)`` runs SART with the reward-head PRM on seeded
    bf16 weights: every request completes and no failure domain fired."""
    out = srv.serve(policy="sart", n=4, num_requests=3, rate_gap=2,
                    ckpt=None, prm_kind="head", window=4, max_tokens=24,
                    max_slots=8, seed=0, temperature=0.9,
                    model_cfg=smoke("qwen2-0.5b"))
    assert out["model"] == "qwen2-0.5b-smoke"
    assert out["completed_requests"] == 3
    assert out["unfinished_requests"] == 0
    assert out["decode_steps"] > 0
    assert out["generated_tokens"] >= out["decode_steps"]
    f = out["faults"]
    assert (f["step_faults"], f["engine_restarts"], f["retries"],
            f["quarantined_requests"]) == (0, 0, 0, 0)


@pytest.mark.parametrize("dtype,rtol", [
    # the chip check's own bound (bf16 weights, activations and KV)
    (jnp.bfloat16, chip_smoke.LOGIT_RTOL),
    # float32 end to end: only summation order differs from the reference
    (jnp.float32, 1e-4),
])
def test_engine_logits_match_float32_reference(dtype, rtol):
    cfg = smoke("qwen2-0.5b")
    model = Model(cfg, dtype=dtype)
    params = model.init_params(jax.random.PRNGKey(0))
    engine = srv.make_engine(model, params, None, max_slots=4, seed=0,
                             temperature=0.0)
    prompt = tasks.gen_problem(np.random.default_rng(0)).prompt_tokens()
    diff, scale = chip_smoke.logits_vs_reference(model, params, engine,
                                                 prompt, steps=6)
    assert scale > 0
    assert diff <= rtol * scale, (diff, scale)
    assert engine.allocator.used_pages == 0


@pytest.fixture
def restore_cache_config():
    from jax.experimental.compilation_cache import compilation_cache
    saved = (jax.config.jax_compilation_cache_dir,
             jax.config.jax_persistent_cache_min_compile_time_secs)
    yield
    jax.config.update("jax_compilation_cache_dir", saved[0])
    jax.config.update("jax_persistent_cache_min_compile_time_secs", saved[1])
    compilation_cache.reset_cache()


def test_compile_cache_follows_env(tmp_path, monkeypatch,
                                   restore_cache_config):
    """With JAX_COMPILATION_CACHE_DIR set, compiled programs land there."""
    from jax.experimental.compilation_cache import compilation_cache
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    compilation_cache.reset_cache()
    assert srv.setup_compile_cache() == str(tmp_path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.jit(lambda x: jnp.cos(x) * 3.0 + 7.0)(jnp.ones((5, 3))) \
        .block_until_ready()
    assert any(tmp_path.iterdir())


def test_compile_cache_default_is_fixed_repo_path(monkeypatch,
                                                  restore_cache_config):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    path = srv.setup_compile_cache()
    assert path == str(ROOT / ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == path
