import os
import sys

# Tests run on the single real CPU device.
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "slow: takes several seconds on CPU (deselect with -m 'not slow')")
