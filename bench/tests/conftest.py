import pytest

from bench import harness


@pytest.fixture(autouse=True)
def no_compile_cache(monkeypatch):
    """Tests leave JAX's persistent compilation cache off, as the program's do."""
    monkeypatch.setattr(harness, "enable_caches", lambda: None)
