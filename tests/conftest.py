import importlib.util
import os
import subprocess
import sys

sys.path.insert(0, os.path.dirname(__file__))

CHIP_SMOKE = os.path.join(os.path.dirname(os.path.abspath(__file__)), "..",
                          "chip_smoke.py")


def load_chip_smoke():
    """The repo-root ``chip_smoke.py`` script, imported as a module (its
    phase functions and constants; its entry point stays unrun)."""
    spec = importlib.util.spec_from_file_location("chip_smoke", CHIP_SMOKE)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def run_multidevice(script: str, n_devices: int = 4,
                    timeout: int = 900) -> str:
    """Run ``script`` in a subprocess with ``n_devices`` host-platform
    placeholder devices forced BEFORE jax imports (the elastic-rescale
    pattern of ``test_checkpoint.py``), so the placeholder devices never
    leak into other tests.  PYTHONPATH carries ``src`` plus this tests
    directory (for ``oracle`` / ``stream_differential`` imports); any
    inherited XLA_FLAGS are scrubbed.  Shared by ``test_shard.py`` and
    ``test_stream_differential.py``.
    """
    env = dict(os.environ)
    here = os.path.dirname(__file__)
    env["PYTHONPATH"] = os.pathsep.join(
        [os.path.join(here, "..", "src"), here])
    env.pop("XLA_FLAGS", None)
    prelude = (
        "import os\n"
        f'os.environ["XLA_FLAGS"] = '
        f'"--xla_force_host_platform_device_count={n_devices}"\n')
    r = subprocess.run([sys.executable, "-c", prelude + script], env=env,
                       capture_output=True, text=True, timeout=timeout)
    assert r.returncode == 0, r.stdout + r.stderr
    return r.stdout
