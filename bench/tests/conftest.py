import os
import sys
from pathlib import Path

os.environ.setdefault("JAX_PLATFORMS", "cpu")
_ROOT = Path(__file__).resolve().parents[2]
for p in (str(_ROOT / "src"), str(_ROOT)):
    if p not in sys.path:
        sys.path.insert(0, p)


import pytest  # noqa: E402


@pytest.fixture(autouse=True)
def _keep_compile_cache_setting(monkeypatch):
    """A run points JAX's compile cache into its checkout; put the process's
    own setting back after each test."""
    import jax

    from repro.launch.compile_cache import ENV_VAR

    monkeypatch.setenv(ENV_VAR, os.environ.get(ENV_VAR, ""))
    before = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", before)
