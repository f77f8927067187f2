"""Shared fixtures of the benchmark's CPU tests."""
import sys
from pathlib import Path

import jax
import pytest

REPO = Path(__file__).resolve().parents[2]
if str(REPO / "bench") not in sys.path:
    sys.path.insert(0, str(REPO / "bench"))


@pytest.fixture
def own_cache(tmp_path, monkeypatch):
    """A harness run turns JAX's persistent compilation cache on; keep its
    programs in a directory of the test's own and restore the settings."""
    from jax.experimental.compilation_cache import compilation_cache

    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "jc"))
    saved = {k: getattr(jax.config, k) for k in (
        "jax_compilation_cache_dir",
        "jax_persistent_cache_min_compile_time_secs")}
    yield
    for k, v in saved.items():
        jax.config.update(k, v)
    compilation_cache.reset_cache()
