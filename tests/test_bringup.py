"""Bring-up contracts: what a run on the chip machine depends on.

- the compile cache goes where ``JAX_COMPILATION_CACHE_DIR`` says, and
  to the fixed ``<repo>/.jax_cache`` otherwise;
- the native library is keyed by its source, compile command and host
  CPU, so a stale or foreign build is never loaded;
- the children the REST, upgrade, watch-herd and replay harnesses spawn
  import no JAX (a chip belongs to one process);
- ``chip_smoke.py`` refuses to report success without a TPU.
"""

import os
import shutil
import subprocess
import sys

import pytest

from kubernetes_tpu import native

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _python(code, env=None, cwd=REPO):
    full = dict(os.environ, JAX_PLATFORMS="cpu", **(env or {}))
    return subprocess.run([sys.executable, "-c", code], cwd=cwd, env=full,
                          capture_output=True, text=True, timeout=120)


@pytest.mark.parametrize("from_env", [True, False],
                         ids=["env-set", "env-unset"])
def test_compile_cache_dir(tmp_path, from_env):
    env = {"JAX_COMPILATION_CACHE_DIR": str(tmp_path) if from_env else ""}
    proc = _python("import jax, kubernetes_tpu.ops\n"
                   "print(jax.config.jax_compilation_cache_dir)", env)
    assert proc.returncode == 0, proc.stderr
    want = str(tmp_path) if from_env else os.path.join(REPO, ".jax_cache")
    assert proc.stdout.strip().splitlines()[-1] == want


@pytest.fixture
def native_copy(tmp_path, monkeypatch):
    src = tmp_path / "solver.cc"
    shutil.copy(native._SRC, src)
    monkeypatch.setattr(native, "_DIR", str(tmp_path))
    monkeypatch.setattr(native, "_SRC", str(src))
    return src


def test_native_library_name_follows_source(native_copy):
    before = native.lib_path()
    native_copy.write_text(native_copy.read_text() + "\n// edited\n")
    after = native.lib_path()
    assert before != after
    assert os.path.basename(after).startswith("libktpu_solver-")


def test_native_library_name_follows_host_cpu(native_copy, monkeypatch):
    here = native.lib_path()
    monkeypatch.setattr(native, "_cpu_flags", lambda: "fpu sse2")
    assert native.lib_path() != here


def test_stale_native_library_is_never_loaded(native_copy):
    """Libraries built from another source, or under the old unkeyed
    name, sit next to the source; the loader's path is neither."""
    stale = native.lib_path()
    native_copy.write_text(native_copy.read_text() + "\n// edited\n")
    for name in (stale, os.path.join(native._DIR, "libktpu_solver.so")):
        with open(name, "wb") as f:
            f.write(b"not a shared object")
    assert native.lib_path() not in (
        stale, os.path.join(native._DIR, "libktpu_solver.so"))
    assert not os.path.exists(native.lib_path())


@pytest.mark.parametrize("module", [
    "kubernetes_tpu.harness.rest_perf",
    "kubernetes_tpu.harness.upgrade",
    "kubernetes_tpu.harness.watchherd",
    "kubernetes_tpu.workloads.replay_bench",
])
def test_spawned_children_import_no_jax(module):
    proc = _python(f"import sys, {module}\n"
                   "print('jax' in sys.modules)")
    assert proc.returncode == 0, proc.stderr
    assert proc.stdout.strip() == "False"


def test_chip_smoke_refuses_without_a_tpu():
    proc = subprocess.run(
        [sys.executable, "chip_smoke.py"], cwd=REPO,
        env=dict(os.environ, JAX_PLATFORMS="cpu"),
        capture_output=True, text=True, timeout=120)
    assert proc.returncode != 0
    assert "no TPU found" in proc.stderr
    assert '"ok"' not in proc.stdout


def test_chip_smoke_alone_fails(tmp_path):
    shutil.copy(os.path.join(REPO, "chip_smoke.py"), tmp_path)
    proc = subprocess.run(
        [sys.executable, "chip_smoke.py"], cwd=tmp_path,
        env=dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=""),
        capture_output=True, text=True, timeout=120)
    assert proc.returncode != 0
    assert '"ok"' not in proc.stdout
