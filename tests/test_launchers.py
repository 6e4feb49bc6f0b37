"""The production launchers run end-to-end on CPU (reduced configs)."""

import os
import subprocess
import sys
import textwrap
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parent.parent

# Runs in its own process: the CPU device count is fixed when JAX starts.
_MESH_PROBE = textwrap.dedent("""
    import os, sys
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
    from repro.configs import get_config, get_reduced
    from repro.launch import train as launcher
    arch, mesh, batch, ckpt = sys.argv[1:]
    # reduced widths with the published vocabulary (odd for whisper-tiny)
    launcher.get_reduced = lambda a: get_reduced(a).replace(
        vocab_size=get_config(a).vocab_size)
    result = launcher.run(launcher.parse_args([
        "--arch", arch, "--reduced", "--steps", "2", "--batch", batch,
        "--seq", "16", "--mesh", mesh, "--ckpt-dir", ckpt]))
    assert [h["step"] for h in result.history] == [0, 1], result.history
    print("MESH_OK", result.history[-1]["loss"])
""")


@pytest.mark.parametrize("arch,mesh,batch", [
    ("whisper-tiny", "1x2", "2"),    # vocab 51865 on a model axis of 2
    ("dlrm-mlp", "2x1", "3"),        # batch 3 on a data axis of 2
])
def test_train_launcher_mesh_axis_not_dividing(tmp_path, arch, mesh, batch):
    """A mesh axis that does not divide a dimension is dropped from its
    sharding, in the state and in the batch, instead of failing the jit."""
    probe = tmp_path / "probe.py"
    probe.write_text(_MESH_PROBE)
    env = dict(os.environ, PYTHONPATH=str(REPO / "src"), JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, str(probe), arch, mesh, batch, str(tmp_path / "ck")],
        capture_output=True, text=True, env=env, timeout=300)
    assert "MESH_OK" in out.stdout, out.stderr[-3000:]


@pytest.mark.slow
def test_train_launcher(tmp_path, capsys):
    from repro.launch.train import main
    rc = main(["--arch", "smollm-135m", "--reduced", "--steps", "12",
               "--batch", "2", "--seq", "16", "--ckpt-dir", str(tmp_path),
               "--ckpt-every", "5"])
    assert rc == 0
    out = capsys.readouterr().out
    assert "CE" in out and "bound" in out     # ran + ridgeline report
    import os
    assert any(n.startswith("step_") for n in os.listdir(tmp_path))


@pytest.mark.slow
def test_serve_launcher(capsys):
    from repro.launch.serve import main
    rc = main(["--arch", "smollm-135m", "--reduced", "--batch", "2",
               "--prompt-len", "4", "--new-tokens", "6"])
    assert rc == 0
    assert "tok/s" in capsys.readouterr().out


@pytest.mark.parametrize("env_dir", [None, "/elsewhere/cache"])
def test_compile_cache_placement(monkeypatch, env_dir):
    """The entry points' cache: JAX's own variable wins, else .jax_cache/."""
    import jax
    from repro.compile_cache import CACHE_DIR, use_compile_cache
    if env_dir is None:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    else:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", env_dir)
    before = jax.config.jax_compilation_cache_dir
    try:
        use_compile_cache()
        after = jax.config.jax_compilation_cache_dir
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
    if env_dir is None:
        assert after == str(CACHE_DIR)
        assert CACHE_DIR.name == ".jax_cache"
        assert (CACHE_DIR.parent / "chip_smoke.py").exists()
    else:
        assert after == before      # left to JAX, which reads the variable
