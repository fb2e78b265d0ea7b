"""The rules that keep a run from hiding which device it was on.

chip_smoke.py refuses to start without a TPU; the compile cache sits where
the operator put it or at one fixed path; the native replay core is built for
this host or raises; the eval CLI errors on a missing checkpoint.
"""

import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_chip_smoke_fails_at_the_device_gate_on_cpu(tmp_path):
    p = subprocess.run(
        [sys.executable, os.path.join(REPO, "chip_smoke.py")],
        env=dict(os.environ, JAX_PLATFORMS="cpu"), cwd=str(tmp_path),
        capture_output=True, text=True, timeout=120,
    )
    assert p.returncode != 0
    assert "platform 'cpu'" in p.stderr and "nothing was run" in p.stderr
    assert p.stdout == ""  # no phase started, no result line


def test_compile_cache_respects_the_operators_placement(monkeypatch):
    import jax

    from rainbow_iqn_apex_tpu.utils import compile_cache

    before = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/somewhere/else")
        assert compile_cache.enable_compile_cache() == "/somewhere/else"
        assert jax.config.jax_compilation_cache_dir == before  # untouched

        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        want = os.path.join(REPO, ".jax_cache")
        assert compile_cache.enable_compile_cache() == want
        assert jax.config.jax_compilation_cache_dir == want
    finally:
        jax.config.update("jax_compilation_cache_dir", before)


@pytest.fixture
def fresh_native(monkeypatch):
    """replay.native with its once-per-process outcome forgotten."""
    from rainbow_iqn_apex_tpu.replay import native

    monkeypatch.setattr(native, "_lib", None)
    monkeypatch.setattr(native, "_error", None)
    return native


def test_native_core_raises_with_the_compilers_stderr(fresh_native, monkeypatch,
                                                      tmp_path):
    native = fresh_native
    bad = tmp_path / "broken.cc"
    bad.write_text("this is not C++\n")
    monkeypatch.setattr(native, "_SRCS", (str(bad),))
    monkeypatch.setattr(native, "_so_path", lambda: str(tmp_path / "_replay_x.so"))
    with pytest.raises(native.NativeBuildError, match="(?s)g\\+\\+ exited .*error"):
        native._build_and_load()
    assert not native.native_available()  # the outcome sticks: no second g++ run
    assert not list(tmp_path.glob("*.so*"))

    from rainbow_iqn_apex_tpu.replay.buffer import PrioritizedReplay

    with pytest.raises(native.NativeBuildError):  # asked for native: no NumPy stand-in
        PrioritizedReplay(1024, (8, 8), lanes=4, use_native=True)
    mem = PrioritizedReplay(1024, (8, 8), lanes=4, use_native=False)
    assert type(mem.tree).__name__ == "SumTree"


def test_native_core_never_loads_another_hosts_binary(fresh_native, monkeypatch):
    native = fresh_native
    here = native._so_path()
    monkeypatch.setattr(native, "_host_signature", lambda: b"some other cpu")
    elsewhere = native._so_path()
    assert elsewhere != here  # a copied checkout's .so has another name ...
    try:
        native._build_and_load()  # ... so this host builds, and loads, its own
        assert native.loaded_library() == elsewhere
    finally:
        if os.path.exists(elsewhere):
            os.remove(elsewhere)


def test_eval_cli_errors_on_a_missing_checkpoint(tmp_path):
    import test_agent

    argv = ["--env-id", "toy:catch", "--run-id", "never_trained",
            "--checkpoint-dir", str(tmp_path / "ckpt")]
    with pytest.raises(FileNotFoundError, match="no checkpoint under"):
        test_agent.main(argv)
    assert not (tmp_path / "ckpt").exists()  # and leaves nothing behind
