"""What chip_smoke.py relies on, checked on the CPU: the backend refusal, the
comparison functions (called directly at a small size — no flag turns the
backend check off), the compile-cache helper, and the native loader's
reported reason."""

import importlib.util
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def chip_smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", os.path.join(REPO, "chip_smoke.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_refuses_without_a_tpu_and_names_the_check():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "chip_smoke.py"),
         "--rows", "200000"],
        env=env, cwd=REPO, capture_output=True, text=True, timeout=120)
    assert proc.returncode != 0
    assert "jax.default_backend() is 'cpu', not 'tpu'" in proc.stderr
    assert '"ok"' not in proc.stdout


def test_comparisons_pass_at_200k_rows(chip_smoke):
    smoke = chip_smoke.Smoke()
    corpus = chip_smoke.make_corpus(200_000, seed=1234)
    ds = chip_smoke.load_store(smoke, corpus)
    try:
        chip_smoke.serve_and_compare(smoke, ds, corpus,
                                     chip_smoke.Reference(corpus))
    finally:
        ds.close()
        # the burst's compile-slow counts must not read as an SLO burn or a
        # hot tenant to later tests of the process-global doctor
        from geomesa_tpu.metrics import REGISTRY
        from geomesa_tpu.obs.workload import WORKLOAD
        REGISTRY.reset()
        WORKLOAD.clear()
    assert smoke.failures == []
    assert {"first_query_with_compile", "same_query_warm",
            "concurrent_64_counts", "count_after_write"} <= set(smoke.phases)


def test_compile_cache_helper(monkeypatch):
    import jax

    from geomesa_tpu import config

    updates = []
    monkeypatch.setattr(jax.config, "update",
                        lambda k, v: updates.append((k, v)))
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/some/dir")
    assert config.enable_compile_cache() == "/some/dir"
    assert updates == []          # JAX reads the variable itself
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    want = os.path.join(REPO, ".jax_cache")
    assert config.enable_compile_cache() == want
    assert updates == [("jax_compilation_cache_dir", want)]


def test_native_load_reports_the_compilers_error(monkeypatch, tmp_path):
    from geomesa_tpu import native

    cxx = tmp_path / "cxx"
    cxx.write_text("#!/bin/sh\necho 'boom: this compiler is broken' >&2\n"
                   "exit 1\n")
    cxx.chmod(0o755)
    monkeypatch.setenv("CXX", str(cxx))
    monkeypatch.delenv("GEOMESA_TPU_NO_NATIVE", raising=False)
    monkeypatch.setattr(native, "_SO", str(tmp_path / "_encode.so"))
    monkeypatch.setattr(native, "_lib", None)
    monkeypatch.setattr(native, "_load_failed", False)
    monkeypatch.setattr(native, "_fallback_reason", None)
    with pytest.warns(RuntimeWarning, match="boom: this compiler is broken"):
        assert native._load() is None
    assert "boom: this compiler is broken" in native.fallback_reason()
    assert native.z2_encode([0.0], [0.0]) is None   # numpy path serves
