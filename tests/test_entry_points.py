"""What every entry point shares: the compile-cache policy, kernels that
initialise no backend on import, and a benchmark harness that fails when a
suite fails.  Cases that need a fresh JAX run in a child process."""

import json
import os
import subprocess
import sys
import textwrap
import types

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _child(code: str, **env) -> dict:
    full = dict(os.environ, PYTHONPATH=os.path.join(REPO, "src"), JAX_PLATFORMS="cpu", **env)
    if "JAX_COMPILATION_CACHE_DIR" not in env:
        full.pop("JAX_COMPILATION_CACHE_DIR", None)
    out = subprocess.run(
        [sys.executable, "-c", textwrap.dedent(code)], capture_output=True, text=True,
        env=full, timeout=300,
    )
    assert out.returncode == 0, out.stderr[-3000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


def test_compile_cache_uses_the_environment_dir_and_nothing_else(tmp_path):
    res = _child(
        """
        import json, os
        import jax, jax.numpy as jnp
        from repro.launch import compile_cache as cc

        before = set(os.listdir(cc.CHECKOUT_CACHE_DIR)) if os.path.isdir(cc.CHECKOUT_CACHE_DIR) else set()
        got = cc.enable_compile_cache()
        # cache every compile, however short, so this one lands on disk
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
        jax.jit(lambda x: jnp.sin(x) * 3.0)(jnp.ones((64,))).block_until_ready()
        after = set(os.listdir(cc.CHECKOUT_CACHE_DIR)) if os.path.isdir(cc.CHECKOUT_CACHE_DIR) else set()
        print(json.dumps({"got": got, "config": jax.config.jax_compilation_cache_dir,
                          "checkout_new": sorted(after - before)}))
        """,
        JAX_COMPILATION_CACHE_DIR=str(tmp_path),
    )
    assert res["got"] == res["config"] == str(tmp_path)
    assert os.listdir(tmp_path)  # entries written where the variable says
    assert res["checkout_new"] == []  # and nowhere else


def test_compile_cache_defaults_to_a_fixed_checkout_path():
    res = _child(
        """
        import json
        import jax
        from repro.launch import compile_cache as cc

        print(json.dumps({"got": cc.enable_compile_cache(),
                          "config": jax.config.jax_compilation_cache_dir}))
        """
    )
    assert res["got"] == res["config"] == os.path.join(REPO, ".jax_cache")


def test_importing_the_kernels_initialises_no_backend():
    res = _child(
        """
        import json
        from jax._src import xla_bridge
        import repro.kernels.grouped_sumvec.ops
        import repro.kernels.paged_attention.ops
        import repro.kernels.sumvec_fft.ops
        import repro.kernels.xcorr_offdiag.ops

        print(json.dumps({"initialised": xla_bridge.backends_are_initialized()}))
        """
    )
    assert res["initialised"] is False


def test_benchmark_harness_fails_when_a_suite_raises(monkeypatch, tmp_path, capsys):
    from benchmarks import run as harness

    def boom():
        raise RuntimeError("suite broke")

    monkeypatch.setitem(sys.modules, "fake_ok_suite", types.SimpleNamespace(run=lambda: ["ok/row,1,x"]))
    monkeypatch.setitem(sys.modules, "fake_bad_suite", types.SimpleNamespace(run=boom))
    monkeypatch.setattr(harness, "SUITES", {"ok": "fake_ok_suite", "bad": "fake_bad_suite"})
    # a set variable keeps the harness's cache helper from touching jax.config
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    monkeypatch.setattr(sys, "argv", ["run", "bad", "ok"])
    assert harness.main() == 1
    out = capsys.readouterr()
    assert "ok/row,1,x" in out.out  # the other suites still ran
    assert "bad/ERROR" in out.out and "FAILED suites: bad" in out.err
    monkeypatch.setattr(sys, "argv", ["run", "ok"])
    assert harness.main() == 0


def test_chip_smoke_refuses_the_cpu():
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "chip_smoke.py")], capture_output=True, text=True,
        env=dict(os.environ, JAX_PLATFORMS="cpu"), timeout=300,
    )
    assert out.returncode != 0
    assert '"ok"' not in out.stdout
