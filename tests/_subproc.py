"""Run a python snippet in a subprocess with N fake XLA devices.

The child is pinned to the CPU backend: N virtual host devices are the
point, and a child must never try to open an accelerator its parent (or
another test worker) may hold."""
import os
import subprocess
import sys

SRC = os.path.join(os.path.dirname(__file__), "..", "src")

HEADER = """\
import os
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count={n}"
import warnings
warnings.filterwarnings("ignore")
import sys
sys.path.insert(0, {src!r})
"""


def run_devices(snippet: str, n: int = 8, timeout: int = 360) -> str:
    code = HEADER.format(n=n, src=os.path.abspath(SRC)) + snippet
    proc = subprocess.run([sys.executable, "-c", code],
                          capture_output=True, text=True, timeout=timeout)
    assert proc.returncode == 0, \
        f"subprocess failed:\nSTDOUT:\n{proc.stdout}\nSTDERR:\n{proc.stderr}"
    return proc.stdout
