"""``chip_smoke.py`` off the chip: it refuses the CPU, and its phases run
end to end at smoke sizes (the CPU rehearsal of the chip run).  Also the
compile-cache helper the entry points call, and the worker pool's
pinning to the CPU, which keeps its processes off a chip the parent
holds."""
import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import chip_smoke  # noqa: E402


def _env(**extra):
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(ROOT, "src")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    env.update(extra)
    return env


def test_chip_smoke_fails_without_a_chip():
    proc = subprocess.run([sys.executable, os.path.join(ROOT, "chip_smoke.py")],
                          env=_env(JAX_PLATFORMS="cpu"), capture_output=True,
                          text=True, timeout=120, cwd=ROOT)
    assert proc.returncode != 0
    assert '"ok": true' not in proc.stdout
    assert "no tpu device" in proc.stderr


def test_chip_smoke_phases_at_smoke_size():
    chip_smoke.phase_train(smoke=True, steps=4, batch=2, seq=32,
                           platform="cpu")
    chip_smoke.phase_kernels(smoke=True)
    chip_smoke.phase_serve(smoke=True, batch=2, prompt_len=8, gen=4)


def test_chip_smoke_mesh_phase_on_four_host_devices():
    code = ("import chip_smoke; "
            "chip_smoke.phase_mesh(smoke=True, steps=2, batch=4, seq=32)")
    proc = subprocess.run(
        [sys.executable, "-c", code], cwd=ROOT, capture_output=True,
        text=True, timeout=300,
        env=_env(JAX_PLATFORMS="cpu",
                 XLA_FLAGS="--xla_force_host_platform_device_count=4"))
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert "param leaves span [4] devices" in proc.stdout


@pytest.mark.parametrize("env_dir", [True, False], ids=["env", "checkout"])
def test_compile_cache_location(tmp_path, env_dir):
    extra = {"JAX_PLATFORMS": "cpu"}
    if env_dir:
        extra["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path / "cache")
    code = ("import json, jax; from repro.launch.cli import "
            "enable_compile_cache as f; p = f(); "
            "print(json.dumps([p, jax.config.jax_compilation_cache_dir]))")
    proc = subprocess.run([sys.executable, "-c", code], cwd=str(tmp_path),
                          env=_env(**extra), capture_output=True, text=True,
                          timeout=120)
    assert proc.returncode == 0, proc.stderr[-2000:]
    path, configured = json.loads(proc.stdout.strip().splitlines()[-1])
    want = (str(tmp_path / "cache") if env_dir
            else os.path.join(ROOT, ".jax_cache"))
    assert path == configured == want


def test_worker_pool_runs_on_cpu_whatever_the_parent_uses(monkeypatch):
    """A parent running with JAX_PLATFORMS=tpu still spawns CPU workers."""
    from repro.distrib import worker as worker_mod

    seen = []

    class FakeWorker:
        def __init__(self, args, env, **kw):
            seen.append(env["JAX_PLATFORMS"])
            r, w = os.pipe()
            os.write(w, b"WORKER_READY 127.0.0.1:1\n")
            os.close(w)
            self.stdout = os.fdopen(r)

    monkeypatch.setenv("JAX_PLATFORMS", "tpu")
    monkeypatch.setattr(worker_mod.subprocess, "Popen", FakeWorker)
    procs, spec = worker_mod.start_worker_processes(2, timeout=10)
    assert seen == ["cpu", "cpu"] and len(spec.workers) == 2
