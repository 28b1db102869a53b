"""Fixtures of the benchmark harness tests."""
import pytest

from benchfix import make_tree


@pytest.fixture
def tiny_tree(tmp_path):
    """A checkout-like tree with the tiny cell ``tiny.closed``."""
    return make_tree(tmp_path)
