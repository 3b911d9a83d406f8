"""chip_smoke.py and the compile-cache helper it shares with bench.py and
the CLI (ISSUE-21).

The smoke itself only means something on the chip; what the CPU can check
is the script: ``--dry-run`` rehearses every phase at a tiny size with the
kernels interpreted and says ``platform: cpu``; without the flag, on a box
where jax is held to the CPU, it refuses before training anything and
prints no result."""

import json
import os
import subprocess
import sys

import jax

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_SMOKE = os.path.join(_ROOT, "chip_smoke.py")


def _run(*argv, **env):
    return subprocess.run(
        [sys.executable, _SMOKE, *argv], capture_output=True, text=True,
        timeout=900, cwd=_ROOT, env=dict(os.environ, **env))


def test_dry_run_rehearses_every_phase_on_the_cpu():
    proc = _run("--dry-run")
    assert proc.returncode == 0, proc.stdout[-3000:] + proc.stderr[-3000:]
    lines = proc.stdout.strip().splitlines()
    assert "platform: cpu" in lines
    assert "pallas_interpret_mode: True" in lines
    # last line: the verdict, with exactly the keys the chip check reads
    verdict = json.loads(lines[-1])
    assert set(verdict) == {"ok", "device"} and verdict["ok"] is True
    assert set(verdict["device"]) == {"platform", "kind", "count"}
    assert verdict["device"] == {"platform": "cpu", "kind": "cpu", "count": 4}
    # the line before it: the facts
    assert lines[-2].startswith("summary: ")
    summary = json.loads(lines[-2][len("summary: "):])
    assert summary["dry_run"] is True
    assert summary["fp32"]["histogram_impl"] == "pallas"
    assert summary["fp32"]["wave_fused_active"] is True
    assert summary["quantized"]["fused_vs_unfused"]["verdict"] == "identical"
    assert summary["serve"]["int8_traverse_mode"] == "fused"
    assert summary["multichip"]["devices"] == 4
    assert list(summary)[-1] == "claim" and summary["claim"] is None


def test_without_a_chip_it_refuses_before_training():
    for env in ({"JAX_PLATFORMS": "cpu"}, {"JAX_PLATFORMS": "cpu,tpu"}):
        proc = _run(**env)
        assert proc.returncode != 0
        assert proc.stdout == ""            # no result, nothing trained
        assert "only runs on a TPU" in proc.stderr


# ------------------------------------------------- compile-cache helper ----
def test_compile_cache_helper(monkeypatch):
    from lightgbm_tpu.utils import jax_cache

    updates = []            # recorded, not applied: the test process keeps
    monkeypatch.setattr(    # whatever cache configuration it has
        jax.config, "update", lambda name, value: updates.append((name,
                                                                  value)))
    # placed from outside: jax reads the variable itself, code sets no
    # other directory
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/somewhere/else")
    keyed = ("jax_compilation_cache_include_metadata_in_key", True)
    assert jax_cache.enable_compile_cache() == "/somewhere/else"
    assert updates == [keyed]      # phase names are part of the key
    del updates[:]
    # unset: one fixed path inside the checkout, the same on every call
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    fixed = os.path.join(_ROOT, ".jax_cache")
    assert jax_cache.enable_compile_cache() == fixed
    assert jax_cache.enable_compile_cache() == fixed
    assert updates == [keyed, ("jax_compilation_cache_dir", fixed),
                       ("jax_persistent_cache_min_compile_time_secs", 0.0)] * 2


def test_cache_entry_count(tmp_path):
    from lightgbm_tpu.utils.jax_cache import cache_entry_count

    assert cache_entry_count(str(tmp_path / "absent")) == 0
    for name in ("jit_f-abc-cache", "jit_g-def-cache", "jit_f-abc-atime"):
        (tmp_path / name).write_bytes(b"x")
    assert cache_entry_count(str(tmp_path)) == 2
