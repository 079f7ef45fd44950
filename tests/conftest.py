"""Shared test utilities. NOTE: no XLA_FLAGS here — smoke tests and benches
must see 1 device; multi-device tests spawn subprocesses via `run_multidev`.
"""
import os
import subprocess
import sys
import textwrap

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run_multidev(code: str, devices: int = 8, timeout: int = 600):
    """Run `code` in a fresh python on N virtual CPU devices (JAX pinned to
    the CPU, device count appended to XLA_FLAGS); returns stdout. The code
    should print 'PASS' on success."""
    from repro.launch.mesh import cpu_devices_env
    env = {**cpu_devices_env(devices), "PYTHONPATH": os.path.join(REPO, "src")}
    r = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                       capture_output=True, text=True, timeout=timeout, env=env)
    assert r.returncode == 0, f"subprocess failed:\n{r.stdout}\n{r.stderr}"
    assert "PASS" in r.stdout, f"no PASS marker:\n{r.stdout}\n{r.stderr}"
    return r.stdout


@pytest.fixture(scope="session")
def rng():
    import jax
    return jax.random.PRNGKey(0)
