"""Multi-device tests: each payload runs in a subprocess with 8 host
devices (the device count must be pinned before jax initializes, which a
live pytest process cannot do)."""
import os
import subprocess
import sys

import pytest

HERE = os.path.dirname(__file__)
REPO = os.path.dirname(HERE)

PAYLOADS = [
    "sharding_rules",
    "e2e_sharded_train",
    "pipeline_forward",
    "pipeline_grad",
    "flash_decode_sp",
    "compressed_psum",
    "elastic_restore",
]


@pytest.mark.parametrize("name", PAYLOADS)
def test_distributed_payload(name):
    env = dict(os.environ)
    # the child never reaches for an accelerator: a chip belongs to one
    # process, and this parent may hold it
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    proc = subprocess.run(
        [sys.executable, os.path.join(HERE, "_dist_payloads.py"), name],
        capture_output=True, text=True, timeout=900, env=env,
    )
    assert proc.returncode == 0, (
        f"\n--- stdout ---\n{proc.stdout}\n--- stderr ---\n{proc.stderr}")
    assert f"PASS {name}" in proc.stdout
