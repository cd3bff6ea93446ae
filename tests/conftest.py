"""Shared pytest config: registers the ``slow`` marker (multi-device
subprocess tests)."""


def pytest_configure(config):
    config.addinivalue_line(
        "markers", "slow: multi-device subprocess tests (several minutes)")
