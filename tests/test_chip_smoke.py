"""chip_smoke.py and the compile cache it turns on, rehearsed on the CPU:
the smoke refuses any platform but a TPU, its build/serve/check phases pass
at a small size with the resident device path forced on, and the cache sits
where ``JAX_COMPILATION_CACHE_DIR`` says, else at ``<checkout>/.jax_cache``.
"""
import importlib.util
import json
import os
import pathlib
import subprocess
import sys

import pytest

from repro.core import resident as resident_mod

ROOT = pathlib.Path(__file__).resolve().parents[1]


def _load_smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke",
                                                  ROOT / "chip_smoke.py")
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def _run(args, env_extra, drop=()):
    env = {k: v for k, v in os.environ.items() if k not in drop}
    env.update({"PYTHONPATH": str(ROOT / "src"), "JAX_PLATFORMS": "cpu",
                **env_extra})
    return subprocess.run([sys.executable, *args], capture_output=True,
                          text=True, timeout=300, env=env, cwd=ROOT)


def test_chip_smoke_refuses_the_cpu():
    out = _run([str(ROOT / "chip_smoke.py")], {})
    assert out.returncode != 0
    assert "'cpu'" in out.stderr
    assert '"ok"' not in out.stdout


_CACHE_PROBE = ("import jax, json; "
                "from repro.launch.compile_cache import enable_compile_cache; "
                "path = enable_compile_cache(); "
                "print(json.dumps([path, jax.config.jax_compilation_cache_dir,"
                " jax.config.jax_enable_compilation_cache]))")


def test_compile_cache_follows_the_env_var(tmp_path):
    where = str(tmp_path / "cache")
    out = _run(["-c", _CACHE_PROBE], {"JAX_COMPILATION_CACHE_DIR": where})
    assert out.returncode == 0, out.stderr
    assert json.loads(out.stdout.splitlines()[-1]) == [where, where, True]


def test_compile_cache_defaults_to_the_checkout():
    out = _run(["-c", _CACHE_PROBE], {},
               drop=("JAX_COMPILATION_CACHE_DIR",))
    assert out.returncode == 0, out.stderr
    want = str(ROOT / ".jax_cache")
    assert json.loads(out.stdout.splitlines()[-1]) == [want, want, True]


def test_chip_smoke_phases_on_cpu(monkeypatch, tmp_path, capsys):
    """The smoke's build/serve/check at 3,000 records: the device path
    (here the XLA reference, forced on) answers every proxy and agrees with
    the float64 host path in all three modes."""
    import jax
    monkeypatch.setenv(resident_mod.ENV_VAR, "1")
    smoke = _load_smoke()
    sizes = dict(n_records=3000, n_reps=300, k=8, n_train=100,
                 triplet_steps=60)
    line = smoke.run(jax.devices(), sizes, tmp_path, require_pallas=False)
    assert line == {"ok": True, "device": {
        "platform": "cpu", "kind": jax.devices()[0].device_kind, "count": 1}}
    reports = {}
    for text in capsys.readouterr().out.splitlines():
        phase, _, body = text.partition(" ")
        reports.setdefault(phase, []).append(json.loads(body))
    assert [r["request"] for r in reports["[serve]"]] == [
        label for label, _ in smoke.REQUESTS]
    assert all(r["status"] == 200 for r in reports["[serve]"])
    assert any(r["n_cracked"] > 0 for r in reports["[serve]"][:3])
    check, = reports["[check]"]
    assert check["resident"]["fallbacks"] == 0
    assert set(check["agreement"]) == {"numeric", "top1", "categorical"}
    with pytest.raises(smoke.SmokeFailure):
        smoke.require(False, "fails loudly")
