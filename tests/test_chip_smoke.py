"""The chip smoke and the plumbing it stands on, checked on the CPU:
``chip_smoke.py`` refuses to run without a TPU or outside a checkout, its
logits comparison, its ResNet and LM phases at a tiny size, the
compile-cache location, and ``launch/serve.py``'s device selection."""
import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import jax
import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))

import chip_smoke  # noqa: E402
from repro.launch.cache import (CACHE_ENV, DEFAULT_CACHE_DIR,  # noqa: E402
                                enable_compile_cache)


def _run_smoke(cwd: Path, **env):
    e = dict(os.environ, JAX_PLATFORMS="cpu", **env)
    return subprocess.run([sys.executable, str(cwd / "chip_smoke.py")],
                          cwd=cwd, env=e, capture_output=True, text=True,
                          timeout=300)


def test_smoke_refuses_cpu():
    proc = _run_smoke(ROOT)
    assert proc.returncode != 0
    assert "no TPU" in proc.stderr
    assert '"ok"' not in proc.stdout
    assert "platform=cpu" in proc.stdout      # the device line comes first


def test_smoke_refuses_outside_checkout(tmp_path):
    shutil.copy(ROOT / "chip_smoke.py", tmp_path)
    proc = _run_smoke(tmp_path)
    assert proc.returncode != 0
    assert "checkout" in proc.stderr
    assert '"ok"' not in proc.stdout


def test_compare_tolerance():
    ref = np.array([[0.0, 1.0, 10.0], [5.0, 5.01, 0.0]])
    chip_smoke.compare("t", "equal", ref, ref, 1e-3)
    with pytest.raises(RuntimeError, match="rel err"):
        chip_smoke.compare("t", "far", ref + 0.5, ref, 1e-3)
    # row 1's top-2 gap (0.01) is inside the tolerated error: its top-1
    # may flip under rounding alone
    flipped = ref.copy()
    flipped[1, :2] = [5.01, 5.0]
    chip_smoke.compare("t", "flip inside the tolerance", flipped, ref, 1e-2)
    with pytest.raises(RuntimeError, match="non-finite"):
        chip_smoke.compare("t", "nan", ref * np.nan, ref, 1e-3)


def test_logits_model_and_spread_check():
    """The smoke compares logits (the softmax stripped) and refuses a
    reference whose inputs barely differ: a comparison that cannot fail."""
    g, shapes = chip_smoke.logits_model("resnet-18", 32, batch=2)
    (out,) = g.outputs
    assert g.nodes[out].op == "dense" and shapes == {"data": (2, 3, 32, 32)}
    assert all(n.op != "softmax" for n in g.topo_order())
    ref = np.array([[100.0, 50.0, 0.0], [100.0, 49.0, 1.0]])
    chip_smoke.check_spread("t", ref, 1e-3)          # spread 1e-2 > 4e-3
    with pytest.raises(RuntimeError, match="could not fail"):
        chip_smoke.check_spread("t", ref, 1e-2)
    with pytest.raises(RuntimeError, match="could not fail"):
        chip_smoke.check_spread("t", np.repeat(ref[:1], 3, axis=0), 1e-3)


def test_resnet_and_lm_phases_tiny(tmp_path, monkeypatch, capsys):
    """Phases 2 and 4 end to end at a tiny size: compile -> save -> load
    -> AsyncServer with zero searches, logits against the CPU backend."""
    monkeypatch.setattr(chip_smoke, "OUT", tmp_path)
    xs, logits, ref = chip_smoke.resnet_phase("resnet-18", 32, seed=0,
                                              n_requests=10, n_ref=2)
    assert len(xs) == 2 and logits.shape == ref.shape == (2, 1000)
    assert np.isfinite(logits).all()
    chip_smoke.lm_phase(seed=0, prompt_len=6, gen=3)
    out = capsys.readouterr().out
    assert "zero schedule searches on load -> serve" in out
    assert "spread across inputs" in out
    assert "served 10 requests" in out
    assert "all in bucket 8" in out
    assert "equal to LMSession.generate" in out


def test_resnet_phase_fails_a_server_that_swaps_rows(tmp_path, monkeypatch):
    """The served bucket-8 program is held to the tolerance that separates
    inputs: a server handing each request another request's row fails."""
    monkeypatch.setattr(chip_smoke, "OUT", tmp_path)
    real = chip_smoke.serve

    def swapping(sess, xs, **kw):
        outs, st = real(sess, xs, **kw)
        return (outs[::-1] if kw.get("bucket") else outs), st

    monkeypatch.setattr(chip_smoke, "serve", swapping)
    with pytest.raises(RuntimeError, match="served in bucket 8 .*rel err"):
        chip_smoke.resnet_phase("resnet-18", 32, seed=0, n_requests=4,
                                n_ref=2)


def test_four_chip_phase_on_four_host_devices(tmp_path):
    """``--four-chips``' phase at a tiny size on four host CPU devices:
    the sharded bucket, the replicas and the four-worker server each
    against one device."""
    code = (
        "import sys, jax\n"
        f"sys.path.insert(0, {str(ROOT)!r})\n"
        "import chip_smoke\n"
        "from pathlib import Path\n"
        f"chip_smoke.OUT = Path({str(tmp_path)!r})\n"
        "chip_smoke.four_chip_phase('resnet-18', 32, 0, jax.devices())\n")
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=str(ROOT / "src"),
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    proc = subprocess.run([sys.executable, "-c", code], env=env,
                          capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    assert "AsyncServer(workers=4) vs one chip, precision=highest" \
        in proc.stdout
    assert "outputs on chips [0, 1, 2, 3]" in proc.stdout


# ---------------------------------------------------------------------------
# compile cache location
# ---------------------------------------------------------------------------

@pytest.fixture
def restore_cache_dir():
    prev = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", prev)


def test_compile_cache_env_wins(tmp_path, monkeypatch, restore_cache_dir):
    monkeypatch.setenv(CACHE_ENV, str(tmp_path))
    assert enable_compile_cache() == tmp_path
    assert jax.config.jax_compilation_cache_dir == str(tmp_path)


def test_compile_cache_default_is_fixed_in_checkout(monkeypatch,
                                                    restore_cache_dir):
    monkeypatch.delenv(CACHE_ENV, raising=False)
    assert enable_compile_cache() == DEFAULT_CACHE_DIR == ROOT / ".jax_cache"
    assert jax.config.jax_compilation_cache_dir == str(ROOT / ".jax_cache")
    ignored = (ROOT / ".gitignore").read_text().split()
    assert ".jax_cache/" in ignored


def test_compile_cache_written_where_env_says(tmp_path):
    """A fresh process writes its compiled program into the env's dir."""
    code = (
        "import jax, jax.numpy as jnp\n"
        "from repro.launch.cache import enable_compile_cache\n"
        "print(enable_compile_cache())\n"
        "jax.config.update('jax_persistent_cache_min_compile_time_secs', 0)\n"
        "jax.jit(lambda x: x * 2 + 1)(jnp.ones(8)).block_until_ready()\n")
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=str(ROOT / "src"), **{CACHE_ENV: str(tmp_path)})
    proc = subprocess.run([sys.executable, "-c", code], env=env,
                          capture_output=True, text=True, timeout=300)
    assert proc.returncode == 0, proc.stderr
    assert proc.stdout.strip() == str(tmp_path)
    assert any(tmp_path.iterdir()), "nothing was cached"


# ---------------------------------------------------------------------------
# launch/serve.py: --devices selects devices, never fewer than asked
# ---------------------------------------------------------------------------

def test_serve_select_devices():
    from repro.launch.serve import select_devices

    devs = jax.devices()
    assert select_devices() == devs
    assert select_devices(1) == devs[:1]
    with pytest.raises(ValueError, match=f"sees {len(devs)} cpu"):
        select_devices(len(devs) + 1)


@pytest.mark.parametrize("platforms, allowed", [
    (None, True), ("", True), ("cpu", True), ("tpu", False),
    ("tpu,cpu", False)])
def test_serve_exposes_host_cores_unless_another_platform(platforms,
                                                          allowed):
    """``--devices N`` forces N host CPU devices where JAX may run on the
    CPU: ``JAX_PLATFORMS`` unset (a CPU-only host) or cpu alone."""
    from repro.launch.cpu import cpu_platform_allowed

    env = {} if platforms is None else {"JAX_PLATFORMS": platforms}
    assert cpu_platform_allowed(env) is allowed


def test_result_line_is_the_contract():
    """The last line: one JSON object, the device as JAX reports it."""
    from types import SimpleNamespace

    devs = [SimpleNamespace(platform="tpu", device_kind="TPU v5 lite")] * 4
    obj = json.loads(chip_smoke.result_line(devs))
    assert obj == {"ok": True, "device": {"platform": "tpu",
                                          "kind": "TPU v5 lite",
                                          "count": 4}}
