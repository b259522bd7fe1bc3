"""chip_smoke.py's phases on the CPU at the SMOKE preset: the same sessions,
checks and reference comparison the chip run makes at the CHIP preset."""
import importlib
import json
import os
import subprocess
import sys

import numpy as np
import pytest

from repro.configs import registry

ROOT = os.path.join(os.path.dirname(__file__), "..")


@pytest.fixture(scope="module")
def cs():
    sys.path.insert(0, ROOT)
    try:
        return importlib.import_module("chip_smoke")
    finally:
        sys.path.remove(ROOT)


@pytest.fixture(scope="module")
def smoke(cs):
    cfg = registry.get_preset("granite-8b", "smoke")
    return cs.one_chip(cfg, batch=4, max_len=32, steps=12, wire_steps=4)


def test_one_chip_phases_at_smoke(smoke):
    out = smoke
    res = out["modes"]
    rate = res["sync"]["triggered"].mean()
    assert 0.5 < rate < 1.0, "most, not all, steps trigger"
    # the CPU contract: the monitor path is bitwise across all three modes
    for mode in ("scan", "async"):
        np.testing.assert_array_equal(res[mode]["u"], res["sync"]["u"])
        np.testing.assert_array_equal(res[mode]["triggered"],
                                      res["sync"]["triggered"])
    for r in list(res.values()) + [out["wire"]]:
        assert (r["fhat"] <= r["u"]).all()
    # float32 SMOKE: the sync session matches the reference to rounding
    trig = res["sync"]["triggered"]
    np.testing.assert_allclose(res["sync"]["fhat"][trig],
                               out["ref"]["fhat"][trig], atol=1e-5)


@pytest.mark.parametrize("path", ["wire", "scan"])
@pytest.mark.parametrize("error", ["corrector 10% of s", "u one ulp"])
def test_path_checks_catch_a_planted_error(cs, smoke, path, error):
    """The agreement checks the chip run makes (wire, scan and data:4 vs
    sync) fail on a corrector off by 10% of s at one triggered cell, and
    on u off by one float32 ulp at one cell."""
    sync = smoke["modes"]["sync"]
    got = smoke["wire"] if path == "wire" else smoke["modes"]["scan"]
    cell = tuple(np.argwhere(got["triggered"])[0])
    bad = {k: np.copy(v) for k, v in got.items()}
    if error.startswith("corrector"):
        s = registry.get_preset("granite-8b", "smoke").monitor.s
        bad["fhat"][cell] -= np.float32(0.1 * s)
    else:
        bad["u"][cell] = np.nextafter(bad["u"][cell], np.float32(np.inf))
    check = ((lambda r: cs.check_wire(r, sync)) if path == "wire" else
             (lambda r: cs.compare_paths(path, r, sync)))
    check(got)                                  # the real result passes
    with pytest.raises(cs.PhaseError):
        check(bad)


def test_checks_catch_a_wrong_corrector(cs):
    """The reference check fails when the served corrector is off by far
    more than bf16 rounding (a 25% error in s*sigma(v))."""
    rng = np.random.default_rng(0)
    u = rng.standard_normal((4, 8)).astype(np.float32)
    v = rng.standard_normal((4, 8)).astype(np.float32)
    s = 0.2
    corr = s / (1 + np.exp(-v))
    trig = np.ones_like(u, bool)
    ref = {"u": u, "v": v, "fhat": u - corr}
    good = {"u": u, "fhat": (u - corr).astype(np.float32), "triggered": trig}
    cs.check_reference(good, ref, s)
    bad = dict(good, fhat=(u - 1.25 * corr).astype(np.float32))
    with pytest.raises(cs.PhaseError):
        cs.check_reference(bad, ref, s)


def test_mesh_path_on_virtual_devices():
    """--chips 4's comparison, rehearsed on four virtual CPU devices: the
    data:4-sharded session agrees with the one-device session bitwise."""
    script = (
        "import sys; sys.path[:0] = [%r, %r]\n"
        "import chip_smoke as cs\n"
        "from repro.configs import registry\n"
        "cfg = registry.get_preset('granite-8b', 'smoke')\n"
        "out = cs.four_chips(cfg, batch=8, max_len=16, steps=6, n=4)\n"
        "print('MESH_OK', out['one']['triggered'].mean())\n"
    ) % (os.path.join(ROOT, "src"), ROOT)
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    r = subprocess.run([sys.executable, "-c", script], env=env,
                       capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, r.stdout[-3000:] + r.stderr[-3000:]
    assert "MESH_OK" in r.stdout
    assert "bitwise True" in r.stdout


def test_main_refuses_without_tpu(cs, capsys):
    assert cs.main([]) == 1
    out = capsys.readouterr().out
    assert "no TPU found" in out
    assert not any(line.startswith("{") for line in out.splitlines())


def test_script_alone_refuses(tmp_path):
    """Copied away from the repository, the script exits nonzero and
    prints no result."""
    import shutil
    shutil.copy(os.path.join(ROOT, "chip_smoke.py"), tmp_path)
    r = subprocess.run([sys.executable, "chip_smoke.py"], cwd=tmp_path,
                       capture_output=True, text=True, timeout=120,
                       env=dict(os.environ, PYTHONPATH=""))
    assert r.returncode != 0
    assert "no repository" in r.stdout + r.stderr
    assert not any(line.startswith("{") for line in r.stdout.splitlines())
    with pytest.raises(json.JSONDecodeError):
        json.loads((r.stdout.strip().splitlines() or [""])[-1])


def test_compile_cache_placement(monkeypatch):
    """The entry points' cache: where JAX_COMPILATION_CACHE_DIR is set JAX
    reads it and no directory is set in code; otherwise the fixed path
    inside the checkout."""
    import jax
    from repro.launch import compile_cache
    was = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.delenv(compile_cache.ENV, raising=False)
        assert compile_cache.enable() == compile_cache.DEFAULT_DIR
        assert jax.config.jax_compilation_cache_dir == \
            compile_cache.DEFAULT_DIR
        assert os.path.samefile(os.path.dirname(compile_cache.DEFAULT_DIR),
                                ROOT)
        jax.config.update("jax_compilation_cache_dir", "/elsewhere")
        monkeypatch.setenv(compile_cache.ENV, "/elsewhere")
        assert compile_cache.enable() == "/elsewhere"
        assert jax.config.jax_compilation_cache_dir == "/elsewhere"
    finally:
        jax.config.update("jax_compilation_cache_dir", was)


def test_chip_preset_cuts_depth_only():
    import dataclasses
    full = registry.get_preset("granite-8b", "full")
    chip = registry.get_preset("granite-8b", "chip")
    diff = {f.name for f in dataclasses.fields(full)
            if getattr(full, f.name) != getattr(chip, f.name)}
    assert diff == {"n_layers"}
    assert 5 <= chip.n_layers < full.n_layers
    with pytest.raises(ValueError, match="no 'chip' preset"):
        registry.get_preset("zamba2-7b", "chip")
    with pytest.raises(ValueError, match="unknown preset"):
        registry.get_preset("granite-8b", "tiny")
