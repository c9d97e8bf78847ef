import pytest

from bench_tiny import make_tiny_root


@pytest.fixture
def tiny_root(tmp_path):
    return make_tiny_root(tmp_path)
