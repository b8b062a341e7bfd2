"""chip_smoke.py and the compile-cache helper, as far as a CPU can show:
the smoke must refuse anything but a TPU, and its rehearsal must keep
running end to end so the script does not rot between chip runs."""

import json
import os
import shutil
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SMOKE = os.path.join(ROOT, "chip_smoke.py")


def _cpu_env(**extra):
    """This environment, held to the CPU, with the cache placed only by
    ``extra``."""
    env = {k: v for k, v in os.environ.items()
           if k not in ("JAX_COMPILATION_CACHE_DIR", "XLA_FLAGS")}
    env.update(JAX_PLATFORMS="cpu", **extra)
    return env


def _run(args, cwd=ROOT, script=SMOKE, **env):
    return subprocess.run([sys.executable, script] + args, cwd=cwd,
                          env=_cpu_env(**env), capture_output=True,
                          text=True, timeout=300)


def _is_pass_line(line: str) -> bool:
    try:
        return bool(json.loads(line).get("ok"))
    except ValueError:
        return False


class TestChipSmoke:
    def test_cpu_platform_fails_and_says_why(self, tmp_path):
        res = _run([], JAX_COMPILATION_CACHE_DIR=str(tmp_path))
        assert res.returncode != 0
        last = res.stdout.strip().splitlines()[-1]
        assert last.startswith("FAIL: platform is 'cpu'"), res.stdout
        assert "JAX_PLATFORMS='cpu'" in last
        assert not any(_is_pass_line(l) for l in res.stdout.splitlines())

    def test_alone_in_a_directory_fails(self, tmp_path):
        shutil.copy(SMOKE, tmp_path / "chip_smoke.py")
        res = _run([], cwd=str(tmp_path),
                   script=str(tmp_path / "chip_smoke.py"))
        assert res.returncode != 0
        assert "no multiverso_tpu package beside" in res.stdout
        assert not any(_is_pass_line(l) for l in res.stdout.splitlines())

    def test_rehearsal_runs_both_legs_and_never_passes(self, tmp_path):
        res = _run(["--rehearsal", "2"],
                   JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cache"))
        assert res.returncode == 0, res.stdout[-3000:] + res.stderr[-3000:]
        lines = res.stdout.strip().splitlines()
        assert lines[-1] == "REHEARSAL"
        assert not any(_is_pass_line(l) for l in lines)
        assert "platform cpu, device_kind cpu, 2 device(s)" in lines[0]
        for leg in ("device_plane", "device_pairs"):
            assert any(l.startswith(f"leg {leg}: ") for l in lines)
        assert sum("oracle: fetch / apply / get" in l for l in lines) == 2
        # the cache was placed from outside: nothing appears in the checkout
        assert f"compile cache {tmp_path / 'cache'}" in res.stdout
        assert os.listdir(tmp_path / "cache")


_CACHE_CHILD = """
import jax
from multiverso_tpu.utils import compile_cache
print(compile_cache.enable())
print(jax.config.jax_compilation_cache_dir)
print(jax.config.jax_persistent_cache_min_compile_time_secs)
"""


class TestCompileCache:
    def _child(self, **env):
        res = subprocess.run([sys.executable, "-c", _CACHE_CHILD],
                             env=_cpu_env(PYTHONPATH=ROOT, **env),
                             capture_output=True, text=True, timeout=120)
        assert res.returncode == 0, res.stderr[-2000:]
        return res.stdout.strip().splitlines()[-3:]

    def test_default_is_a_fixed_path_in_the_checkout(self):
        used, configured, threshold = self._child()
        assert used == configured == os.path.join(ROOT, ".jax_cache")
        # the row-verb programs compile in under jax's 1 s default
        assert float(threshold) == 0.0

    def test_placed_from_outside_sets_no_path(self, tmp_path):
        used, configured, threshold = self._child(
            JAX_COMPILATION_CACHE_DIR=str(tmp_path))
        # jax read the variable itself; the helper only reports it
        assert used == configured == str(tmp_path)
        assert float(threshold) == 0.0
