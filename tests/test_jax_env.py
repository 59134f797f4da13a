"""Process-entry JAX set-up (dynamo_tpu/utils/jax_env.py) and the chip
smoke's parent-stays-off-JAX contract. Cheap: nothing here compiles an
engine; the whole-script rehearsal is marked slow."""

import glob
import os
import re
import subprocess
import sys

import jax
import pytest

from dynamo_tpu.utils import jax_env

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _sources():
    """Every Python file the repo ships: the package, the tests,
    chip_smoke.py and the driver's entry at the root."""
    files = glob.glob(os.path.join(REPO, "*.py"))
    for top in ("dynamo_tpu", "tests"):
        files += glob.glob(os.path.join(REPO, top, "**", "*.py"), recursive=True)
    return sorted(files)


def test_cache_dir_follows_the_variable_or_the_checkout(monkeypatch):
    before = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv(jax_env.COMPILE_CACHE_ENV, "/some/dir")
        assert jax_env.configure_compile_cache() == "/some/dir"
        # Set: nothing about the directory is set in code (JAX reads the
        # variable itself at start-up).
        assert jax.config.jax_compilation_cache_dir == before

        monkeypatch.delenv(jax_env.COMPILE_CACHE_ENV)
        fixed = os.path.join(os.path.realpath(REPO), ".jax_cache")
        assert jax_env.configure_compile_cache() == fixed
        assert jax.config.jax_compilation_cache_dir == fixed
        assert (
            jax.config.jax_persistent_cache_min_compile_time_secs
            == jax_env.MIN_COMPILE_TIME_SECS
        )
    finally:
        jax.config.update("jax_compilation_cache_dir", before)


def test_only_the_helper_places_the_cache_and_no_absolute_repo_path():
    exempt = {
        os.path.join(REPO, "dynamo_tpu", "utils", "jax_env.py"),
        os.path.abspath(__file__),
    }
    sets_cache = re.compile(r"""update\(\s*["']jax_compilation_cache_dir""")
    offenders = []
    for path in _sources():
        if path in exempt:
            continue
        with open(path, encoding="utf-8") as f:
            text = f.read()
        if sets_cache.search(text):
            offenders.append(f"{path}: sets jax_compilation_cache_dir itself")
        if "/root/repo/" in text:
            offenders.append(f"{path}: holds an absolute /root/repo/ path")
    assert not offenders, "\n".join(offenders)


@pytest.mark.parametrize(
    "backend, env, ok",
    [
        ("tpu", None, True),
        ("tpu", "cpu", True),
        ("cpu", "cpu", True),
        ("cpu", "tpu,cpu", True),
        ("cpu", None, False),  # no chip found, nobody asked for the CPU
        ("cpu", "", False),
        ("gpu", None, False),
        ("gpu", "cpu", False),
    ],
)
def test_serving_platform_is_the_tpu_or_an_explicit_cpu(
    monkeypatch, backend, env, ok
):
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    if env is None:
        monkeypatch.delenv("JAX_PLATFORMS", raising=False)
    else:
        monkeypatch.setenv("JAX_PLATFORMS", env)
    if ok:
        assert jax_env.require_serving_platform() == backend
    else:
        with pytest.raises(RuntimeError, match="JAX_PLATFORMS=cpu"):
            jax_env.require_serving_platform()


def test_engine_refuses_a_platform_nobody_asked_for(monkeypatch):
    """The check sits in DeviceRunner.__init__, before any allocation, so
    every process that builds an engine passes through it."""
    from dynamo_tpu.engines.tpu import JaxEngineArgs
    from dynamo_tpu.engines.tpu.runner import DeviceRunner
    from dynamo_tpu.models.config import tiny_config

    monkeypatch.delenv("JAX_PLATFORMS")
    with pytest.raises(RuntimeError, match="serves from a TPU"):
        DeviceRunner(JaxEngineArgs(config=tiny_config()))


def test_chip_smoke_parent_stays_off_jax():
    """A chip belongs to one process at a time: the smoke's parent must
    import neither jax nor a module that does."""
    code = (
        "import sys; sys.argv = ['chip_smoke.py']; import chip_smoke; "
        "bad = sorted(m for m in sys.modules if m == 'jax' or "
        "m.startswith(('jax.', 'jaxlib'))); "
        "print('LOADED', bad); sys.exit(1 if bad else 0)"
    )
    proc = subprocess.run(
        [sys.executable, "-c", code], cwd=REPO, capture_output=True,
        text=True, timeout=120,
    )
    assert proc.returncode == 0, proc.stdout + proc.stderr


def test_chip_smoke_alone_in_a_directory_fails_without_a_result(tmp_path):
    """Copied out of the repo the script must exit non-zero and print no
    result line (the driver runs it that way and expects the failure)."""
    import shutil

    shutil.copy(os.path.join(REPO, "chip_smoke.py"), tmp_path)
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    proc = subprocess.run(
        [sys.executable, "chip_smoke.py"], cwd=tmp_path, env=env,
        capture_output=True, text=True, timeout=120,
    )
    assert proc.returncode != 0
    assert '"ok"' not in proc.stdout


@pytest.mark.slow
def test_chip_smoke_cpu_rehearsal():
    """The whole script at --model tiny on the CPU: every stage's control
    flow, including the tp and prefill/decode stages over virtual devices.
    Its last line must say it was not a chip run."""
    import json

    proc = subprocess.run(
        [sys.executable, "chip_smoke.py", "--rehearse-cpu"], cwd=REPO,
        capture_output=True, text=True, timeout=1800,
    )
    assert proc.returncode == 0, proc.stdout[-4000:] + proc.stderr[-4000:]
    last = json.loads(proc.stdout.strip().splitlines()[-1])
    assert last["ok"] is False and "NOT a chip run" in last["rehearsal"]
