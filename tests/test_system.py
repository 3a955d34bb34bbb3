"""End-to-end behaviour tests: training improves + survives failure with
bit-identical resume; serving pipeline processes the pressure trajectory
with twin-driven scaling; slurm asset generation."""
import pathlib
import subprocess
import sys

import pytest

import os

CWD = str(pathlib.Path(__file__).resolve().parents[1])
# children stay on the CPU: a TPU belongs to one process, and without a
# platform jax spends minutes probing for accelerators
ENV = {"PYTHONPATH": "src", "PATH": "/usr/bin:/bin",
       "HOME": os.environ.get("HOME", CWD), "JAX_PLATFORMS": "cpu"}


def run(args, timeout=560):
    return subprocess.run([sys.executable, "-m"] + args, capture_output=True,
                          text=True, env=ENV, cwd=CWD, timeout=timeout)


@pytest.mark.slow
def test_train_loss_improves(tmp_path):
    r = run(["repro.launch.train", "--arch", "qwen2-7b", "--reduced",
             "--steps", "40", "--batch", "8", "--seq", "64",
             "--ckpt-dir", str(tmp_path)])
    assert r.returncode == 0, r.stdout + r.stderr
    assert "improved" in r.stdout and "NOT improved" not in r.stdout


@pytest.mark.slow
def test_crash_restart_resumes_identically(tmp_path):
    """Simulated node failure at step 25; restart resumes from step-20
    checkpoint and reaches the same final loss as an uninterrupted run."""
    base = run(["repro.launch.train", "--arch", "qwen2-7b", "--reduced",
                "--steps", "40", "--batch", "4", "--seq", "32",
                "--ckpt-dir", str(tmp_path / "a"), "--ckpt-every", "10"])
    final_line = [l for l in base.stdout.splitlines() if l.startswith("step   39")]
    crash = run(["repro.launch.train", "--arch", "qwen2-7b", "--reduced",
                 "--steps", "40", "--batch", "4", "--seq", "32",
                 "--ckpt-dir", str(tmp_path / "b"), "--ckpt-every", "10",
                 "--kill-at-step", "25"])
    assert "[failure] simulated node loss" in crash.stdout
    resume = run(["repro.launch.train", "--arch", "qwen2-7b", "--reduced",
                  "--steps", "40", "--batch", "4", "--seq", "32",
                  "--ckpt-dir", str(tmp_path / "b"), "--ckpt-every", "10"])
    assert "[restore] resumed from step 20" in resume.stdout
    resumed_line = [l for l in resume.stdout.splitlines()
                    if l.startswith("step   39")]
    assert final_line and resumed_line and final_line == resumed_line


@pytest.mark.slow
def test_walltime_drain_checkpoints_and_exits(tmp_path):
    """§4.5.4: inside the 60s drain margin the trainer checkpoints and
    exits for requeue instead of being killed mid-step."""
    r = run(["repro.launch.train", "--arch", "xlstm-1.3b", "--reduced",
             "--steps", "200", "--batch", "2", "--seq", "32",
             "--ckpt-dir", str(tmp_path), "--walltime", "90",
             "--step-seconds", "1.0"])
    assert r.returncode == 0, r.stdout + r.stderr
    assert "[drain] checkpointed" in r.stdout
    from repro.checkpoint import checkpointer as ckpt
    assert ckpt.latest_step(tmp_path) is not None


@pytest.mark.slow
def test_serve_e2e_twin_scales(tmp_path):
    r = run(["repro.launch.serve", "--arch", "qwen2-7b", "--reduced",
             "--devices", "8",
             "--tp", "2", "--nodes", "4", "--ticks", "40",
             "--kernel-mode", "auto"], timeout=560)
    assert r.returncode == 0, r.stdout + r.stderr
    # the kernel dispatch mode is resolved and logged before any tracing
    assert "[kernels] mode=auto (resolved " in r.stdout
    assert "[done] served=" in r.stdout
    # the twin escalated at least once under the pressure trajectory
    assert "scale events=[(0.0, 0, 1)" in r.stdout
    assert ", 1, 2)" in r.stdout


SMOKE_LOGITS = """
import json, sys
sys.path.insert(0, ".")
import chip_smoke
from repro.launch import serve
eng = serve.main(["--arch", "qwen2-7b", "--reduced", "--nodes", "1",
                  "--paged", "--vary-shapes", "--ticks", "2",
                  "--lam-scale", "0.006"])
print(json.dumps(chip_smoke.logits_phase(eng.serving, eng.runtime_cfg)))
"""


def test_smoke_logits_check_separates_sound_from_broken():
    """chip_smoke's logits check at the reduced size: the served runtime's
    last-step logits match the uncached forward, and both broken controls
    (shifted page table, zeroed KV head) read far above the tolerance."""
    import json
    sys.path.insert(0, CWD)
    from chip_smoke import LOGIT_TOL, N_PROMPTS
    r = subprocess.run([sys.executable, "-c", SMOKE_LOGITS],
                       capture_output=True, text=True, env=ENV, cwd=CWD,
                       timeout=300)
    assert r.returncode == 0, r.stdout + r.stderr
    errs = json.loads(r.stdout.strip().splitlines()[-1])
    # float32 at the reduced size: the sound paths agree to rounding
    for k in ("pallas_vs_ref", "jnp_vs_ref", "pallas_vs_jnp"):
        assert errs[k] <= LOGIT_TOL / 100, errs
    assert errs["rows_same_tokens"] == N_PROMPTS
    for k in ("control_shifted_pages", "control_zeroed_head"):
        assert errs[k] > 2 * LOGIT_TOL, errs


def test_slurm_asset_generation(tmp_path):
    from repro.launch.slurm import generate
    files = generate(tmp_path, nodes=40, walltime="03:00:00")
    assert set(files) == {"deploy-serving.sh", "nersc-slurm.sh",
                          "node-setup.sh"}
    slurm = (pathlib.Path(tmp_path) / "nersc-slurm.sh").read_text()
    assert "#SBATCH -N 40" in slurm and "sleep 3" in slurm
    node = (pathlib.Path(tmp_path) / "node-setup.sh").read_text()
    # §4.5.4: JIRIAF walltime = slurm walltime - 60s
    assert 'JIRIAF_WALLTIME="10740"' in node
    assert "ssh -NfL $APISERVER_PORT" in node
    assert "ssh -NfR $KUBELET_PORT" in node


@pytest.mark.parametrize("from_env", [True, False])
def test_compile_cache_location(monkeypatch, tmp_path, from_env):
    """Entry points keep compiled programs in ``$JAX_COMPILATION_CACHE_DIR``
    when it is set (and set no path of their own), else in the checkout's
    fixed ``.jax_cache``; every program is written, however fast."""
    import jax
    from repro.launch.compile_cache import enable_compile_cache
    names = ("jax_compilation_cache_dir",
             "jax_persistent_cache_min_compile_time_secs")
    saved = {k: getattr(jax.config, k) for k in names}
    if from_env:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    else:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    try:
        path = enable_compile_cache()
        if from_env:
            assert path == str(tmp_path)
            assert jax.config.jax_compilation_cache_dir == saved[names[0]]
        else:
            assert path == str(pathlib.Path(CWD) / ".jax_cache")
            assert jax.config.jax_compilation_cache_dir == path
        assert jax.config.jax_persistent_cache_min_compile_time_secs == 0
    finally:
        for k, v in saved.items():
            jax.config.update(k, v)
