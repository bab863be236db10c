"""Run a snippet under a fake multi-device JAX runtime (subprocess so the
parent's 1-device jax is untouched)."""
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run_with_devices(code: str, n_devices: int, timeout: int = 560) -> str:
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"          # emulated devices, never a chip
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={n_devices}"
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    proc = subprocess.run([sys.executable, "-c", code], env=env,
                          capture_output=True, text=True, timeout=timeout)
    assert proc.returncode == 0, \
        f"subprocess failed:\nSTDOUT:{proc.stdout}\nSTDERR:{proc.stderr[-3000:]}"
    return proc.stdout
