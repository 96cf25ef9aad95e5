"""chip_smoke.py off the chip: legs A and B in-process at the script's tiny
size on the CPU mesh, the device rule of the default invocation, the
compile-cache rule both ways, and the train entry point's refusal to run
without the native library it was asked for."""

import json
import os
import sys

import pytest

import jax

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import chip_smoke
from swiftsnails_tpu.utils import compile_cache
from swiftsnails_tpu.utils.config import Config


def test_legs_a_and_b_tiny(tmp_path):
    """train -> checkpoint -> serve through the entry points, seconds on CPU
    (interpret-mode kernels; the Mosaic and device assertions need the chip)."""
    from swiftsnails_tpu.utils.watchdog import Watchdog

    wd = Watchdog("test")
    try:
        work = tmp_path / "work"
        work.mkdir()
        a = chip_smoke.leg_train("tiny", str(work), wd, str(tmp_path))
        rep = a["report"]
        assert rep["vocab"] == rep["capacity"] == 2048  # ids span the table
        assert rep["mosaic_custom_calls"] == 0  # CPU: nothing to find
        assert os.path.isdir(a["ckpt_root"])
        b = chip_smoke.leg_serve(a, wd)
        assert b["pulled_rows"] == 2 * len(a["ids"])
        # the output directory got small text only
        losses = chip_smoke.read_losses(str(tmp_path / "leg_a_metrics.jsonl"))
        assert len(losses) >= rep["steps"]
    finally:
        wd.close()


def test_default_invocation_fails_off_the_chip(monkeypatch, capsys):
    # conftest pins JAX_PLATFORMS=cpu: the default (full-size) invocation
    # must stop before any work, name the platform, and print no result
    monkeypatch.setattr(compile_cache, "configure_compile_cache", lambda: "")
    rc = chip_smoke.main([])
    out, err = capsys.readouterr()
    assert rc != 0
    assert "platform is 'cpu'" in err and "not 'tpu'" in err
    assert '"ok"' not in out
    assert not any(line.startswith("{") for line in out.splitlines())


def test_compile_cache_is_placed_from_outside(monkeypatch):
    before = jax.config.jax_compilation_cache_dir
    updates = []
    real_update = jax.config.update
    monkeypatch.setattr(
        jax.config, "update",
        lambda k, v: (updates.append((k, v)), real_update(k, v)))
    try:
        # variable set: the program sets nothing (jax reads it itself)
        monkeypatch.setenv(compile_cache.CACHE_ENV, "/some/dir")
        assert compile_cache.configure_compile_cache() == "/some/dir"
        assert updates == []
        assert jax.config.jax_compilation_cache_dir == before
        # unset: the fixed path under the checkout, never a temp dir
        monkeypatch.delenv(compile_cache.CACHE_ENV)
        placed = compile_cache.configure_compile_cache()
        assert placed == os.path.join(ROOT, ".jax_cache")
        assert updates == [("jax_compilation_cache_dir", placed)]
        assert jax.config.jax_compilation_cache_dir == placed
        with open(os.path.join(ROOT, ".gitignore")) as f:
            assert ".jax_cache/" in f.read().split()
    finally:
        real_update("jax_compilation_cache_dir", before)


def test_failed_native_build_raises_on_the_train_entry_point(monkeypatch):
    from swiftsnails_tpu import cli
    from swiftsnails_tpu.data import native

    # a compile line that cannot succeed; the library name is keyed on it,
    # so no earlier build is picked up in its place
    monkeypatch.setattr(native, "_CXX", ["g++", "--no-such-flag-ssn"])
    monkeypatch.setattr(native, "_lib", None)
    monkeypatch.setattr(native, "_build_error", None)
    with pytest.raises(native.NativeBuildError) as e:
        cli._build_trainer(Config({"model": "word2vec", "data": "unused"}))
    msg = str(e.value)
    assert "g++ --no-such-flag-ssn" in msg and "exit" in msg
    assert "no-such-flag-ssn" in msg.split("\n", 1)[1]  # the compiler's own words
    # opting out is explicit, and then nothing is built or raised
    assert native.use_native(Config({"use_native": "0"})) is False


def test_native_library_is_keyed_on_its_source(monkeypatch, tmp_path):
    from swiftsnails_tpu.data import native

    so = native._so_path()
    assert os.path.basename(so).startswith("libsnails-") and so.endswith(".so")
    # other source (or another compile line) is another file: a stale build
    # in a fresh copy of the tree can never be loaded for it
    other = tmp_path / "libsnails.cpp"
    with open(native._SRC) as f:
        other.write_text(f.read() + "\n// changed\n")
    monkeypatch.setattr(native, "_SRC", str(other))
    assert native._so_path() != so


def test_replica_for_the_chip_from_a_parent_holding_it_fails_fast(
        monkeypatch, tmp_path):
    from swiftsnails_tpu.net.fleet import ReplicaSpawner
    from swiftsnails_tpu.utils import platform_pin

    spawned = []
    monkeypatch.setattr(
        "swiftsnails_tpu.net.fleet.subprocess.Popen",
        lambda *a, **kw: spawned.append(a) or (_ for _ in ()).throw(
            AssertionError("must not spawn")))
    monkeypatch.setattr(platform_pin, "holds_accelerator", lambda: True)
    sp = ReplicaSpawner(str(tmp_path), env={"JAX_PLATFORMS": "tpu"})
    with pytest.raises(RuntimeError, match="already holds the accelerator"):
        sp.spawn()
    assert spawned == []
    # this (CPU) test process holds no accelerator
    monkeypatch.undo()
    assert platform_pin.holds_accelerator() is False
    assert platform_pin.cpu_requested() is True
