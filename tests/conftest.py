"""Test-suite bootstrap: puts the tests directory on the import path (for
the helper modules the tests share) and registers the ``slow`` marker."""
import os
import sys

sys.path.insert(0, os.path.dirname(__file__))


def pytest_configure(config):
    config.addinivalue_line(
        "markers", "slow: long-running end-to-end tests (trainer loops)")
