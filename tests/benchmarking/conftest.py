"""The tests' own benchmark root (see ``bench_tiny``)."""
import pytest
from bench_tiny import write_root


@pytest.fixture(scope="module")
def tiny_root(tmp_path_factory):
    return write_root(str(tmp_path_factory.mktemp("bench_root")))
