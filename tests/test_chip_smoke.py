"""chip_smoke.py's contract off the chip, and the no-fallback rules it
rests on: the rehearsal passes here and says "cpu"; the real run refuses
a CPU; nothing on the served path carries on without the chip (or the
native library) unannounced."""

import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _chip_smoke(*args, timeout=600, cache_dir=None):
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    if cache_dir is not None:
        env["JAX_COMPILATION_CACHE_DIR"] = str(cache_dir)
    # conftest's 8 virtual devices are for the in-process tests: the
    # script describes its own deployment.
    env.pop("XLA_FLAGS", None)
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "chip_smoke.py"), *args],
        capture_output=True, text=True, cwd=REPO, env=env, timeout=timeout,
    )
    lines = [json.loads(ln) for ln in out.stdout.splitlines() if ln.strip()]
    return out, lines


def test_rehearsal_passes_and_names_the_cpu(tmp_path):
    # A compile cache of its own: the script counts the directory's new
    # entries over its second start, and the checkout's directory is
    # written by every other test worker that compiles meanwhile.
    out, lines = _chip_smoke("--rehearse", cache_dir=tmp_path / "jax_cache")
    assert out.returncode == 0, out.stdout[-1500:] + out.stderr[-1500:]
    assert lines[-1] == {"ok": True, "device": {"platform": "cpu", "kind": "cpu", "count": 1}}
    phases = {ln["phase"]: ln for ln in lines[:-1]}
    assert all(ln["ok"] is True for ln in phases.values())
    assert {"plan", "native_build", "server", "load", "queries", "device_memory",
            "compile_cache", "kernels"} <= set(phases)
    assert phases["plan"]["reduced"] == [] and phases["plan"]["rehearse"] is True
    assert phases["server"]["device"]["engine"] == "jax"
    assert phases["server"]["device"]["native"] is True
    doors = {f: v["door"] for f, v in phases["load"]["frames"].items()}
    assert doors == {"a": "bulk", "b": "import", "c": "import"}
    # The second start compiled nothing the first had not cached.
    warm = phases["compile_cache"]["warm_start"]
    assert phases["compile_cache"]["new_entries_on_warm_start"] == 0
    assert warm["compilations"] == warm["cache_hits"] > 0
    assert phases["kernels"]["failed"] == [] and phases["kernels"]["interpret"] is True


def test_without_rehearse_a_cpu_is_a_failure():
    out, lines = _chip_smoke(timeout=120)
    assert out.returncode != 0
    assert lines[-1]["ok"] is False and "device" not in lines[-1]
    assert not any(ln.get("phase") == "server" for ln in lines)


def test_auto_engine_raises_when_jax_cannot_initialize(monkeypatch):
    import jax

    from pilosa_tpu import engine

    def no_backend():
        raise RuntimeError("Unable to initialize backend 'tpu': no chip")

    monkeypatch.delenv("PILOSA_TPU_ENGINE", raising=False)
    monkeypatch.setattr(jax, "devices", no_backend)
    with pytest.raises(RuntimeError, match="Unable to initialize backend 'tpu'"):
        engine.new_engine("auto")
    # Only a name chooses the host engine.
    assert isinstance(engine.new_engine("numpy"), engine.NumpyEngine)


def test_backend_probe_does_not_swallow_errors(monkeypatch):
    import jax

    from pilosa_tpu.ops import dispatch

    def broken():
        raise RuntimeError("backend lost")

    dispatch._backend_is_tpu.cache_clear()
    monkeypatch.setattr(jax, "default_backend", broken)
    try:
        with pytest.raises(RuntimeError, match="backend lost"):
            dispatch.use_pallas()
    finally:
        dispatch._backend_is_tpu.cache_clear()


def test_server_workers_with_a_jax_engine_refuses_to_start(monkeypatch, tmp_path, capsys):
    from pilosa_tpu.cli.main import _check_workers, main
    from pilosa_tpu.config import Config

    monkeypatch.setenv("PILOSA_TPU_SERVER_WORKERS", "2")
    monkeypatch.delenv("PILOSA_ENGINE", raising=False)
    monkeypatch.delenv("PILOSA_TPU_ENGINE", raising=False)
    rc = main(["server", "--data-dir", str(tmp_path), "--host", "127.0.0.1:0", "--test-exit"])
    assert rc == 1
    assert "a chip belongs to one process" in capsys.readouterr().err
    assert not os.listdir(tmp_path)  # refused before anything was opened
    with pytest.raises(ValueError, match="engine 'mesh'"):
        _check_workers(Config(engine="mesh", server_workers=2))
    _check_workers(Config(engine="numpy", server_workers=2))  # host-only: allowed
    _check_workers(Config(engine="jax", server_workers=1))


def test_compile_cache_is_placeable_from_outside(monkeypatch, tmp_path):
    import jax

    from pilosa_tpu import engine

    calls = []
    monkeypatch.setattr(jax.config, "update", lambda k, v: calls.append((k, v)))
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert engine.configure_compile_cache() == str(tmp_path)
    assert "jax_compilation_cache_dir" not in dict(calls)
    assert dict(calls)["jax_persistent_cache_min_compile_time_secs"] == 0.0

    calls.clear()
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    want = os.path.join(REPO, ".jax_cache")  # fixed: no pid, no time, no tmp name
    assert engine.configure_compile_cache() == want
    assert dict(calls)["jax_compilation_cache_dir"] == want
    assert engine.configure_compile_cache() == want


def test_status_names_the_device(tmp_path):
    import jax

    from pilosa_tpu import native
    from pilosa_tpu.config import Config
    from pilosa_tpu.server.client import Client
    from pilosa_tpu.server.server import Server

    srv = Server(Config(data_dir=str(tmp_path), host="127.0.0.1:0", engine="jax"))
    srv.open()
    try:
        dev = Client(srv.host).status()["device"]
    finally:
        srv.close()
    assert dev["engine"] == "jax" and dev["platform"] == "cpu"
    assert dev["device_kind"] == jax.devices()[0].device_kind
    assert dev["count"] == len(jax.devices()) == len(dev["devices"])
    assert {"id", "bytes_in_use", "peak_bytes_in_use"} <= set(dev["devices"][0])
    assert dev["native"] is (native.load() is not None)
    assert dev["native_path"] == native.loaded_path()


def test_a_native_library_that_cannot_load_is_an_error(monkeypatch, tmp_path):
    from pilosa_tpu import native

    bad = tmp_path / "libpilosa_native.so"
    bad.write_bytes(b"not an ELF file")
    for name in ("_lib", "_lib_path_loaded"):
        monkeypatch.setattr(native, name, None)
    monkeypatch.setattr(native, "_tried", False)
    monkeypatch.setenv("PILOSA_TPU_NATIVE_LIB", str(bad))
    monkeypatch.delenv("PILOSA_TPU_NO_NATIVE", raising=False)
    for _ in range(2):  # every call, not only the first
        with pytest.raises(RuntimeError, match="failed to load"):
            native.load()
    # The Python lanes serve only when they are asked for.
    monkeypatch.setenv("PILOSA_TPU_NO_NATIVE", "1")
    assert native.load() is None
