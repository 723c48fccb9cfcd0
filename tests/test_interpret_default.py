"""Platform-aware Pallas interpret default (regression).

``flash_attention_pallas`` (and the blocked matmul) used to hardcode
``interpret=True`` — silently running the interpreter even on a TPU host.
The default is now ``interpret=None``: resolved per-platform (compiled on
backends with a Pallas lowering, interpreter elsewhere), with an explicit
bool always winning.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import pltpu_compat
from repro.kernels.flash_attention import flash_attention_pallas
from repro.kernels.pltpu_compat import resolve_interpret
from repro.models.lm.layers import flash_attention_xla


def _fake_backend(monkeypatch, name):
    monkeypatch.setattr(pltpu_compat.jax, "default_backend", lambda: name)


def test_default_interprets_off_tpu(monkeypatch):
    _fake_backend(monkeypatch, "cpu")
    assert resolve_interpret(None) is True
    _fake_backend(monkeypatch, "gpu")
    assert resolve_interpret(None) is True


def test_default_compiles_on_tpu(monkeypatch):
    _fake_backend(monkeypatch, "tpu")
    assert resolve_interpret(None) is False


@pytest.mark.parametrize("backend", ["cpu", "tpu"])
def test_explicit_override_always_wins(monkeypatch, backend):
    _fake_backend(monkeypatch, backend)
    assert resolve_interpret(True) is True
    assert resolve_interpret(False) is False


def test_flash_attention_default_runs_on_host():
    """The public entry point with no interpret argument must work on the
    host backend (the original bug made this depend on a hardcoded True)."""
    key = jax.random.PRNGKey(0)
    q, k, v = (jax.random.normal(jax.random.PRNGKey(i), (1, 2, 128, 16))
               for i in range(3))
    out = flash_attention_pallas(q, k, v, causal=True, bq=64, bkv=64)
    ref = flash_attention_xla(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def _tiny_graph():
    from repro.core.graph import Graph

    g = Graph()
    g.add("in", "input")
    g.add("c1", "conv2d", ["in"], in_channels=3, out_channels=8, kh=3,
          kw=3, stride=1, pad=1)
    g.add("r1", "relu", ["c1"])
    g.add("gap", "global_avg_pool", ["r1"])
    g.add("fl", "flatten", ["gap"])
    g.add("fc", "dense", ["fl"], units=4)
    g.mark_output("fc")
    return g, {"in": (1, 3, 8, 8)}


def test_every_kernel_entry_defaults_to_the_platform():
    """No entry point hardcodes the interpreter: ``interpret`` defaults to
    None (resolved per platform) from ``compile()`` down to the kernels."""
    import inspect

    from repro.engine import InferenceSession, compile as compile_session
    from repro.engine.executor import CompiledModel, compile_model
    from repro.kernels import ops
    from repro.kernels.conv2d_nchwc import conv2d_nchwc_pallas
    from repro.kernels.matmul_blocked import matmul_padded, matmul_pallas
    from repro.kernels.ssd_chunk import ssd_intra_pallas
    from repro.nn import ops as nn_ops

    fns = [compile_session, InferenceSession.__init__, compile_model,
           CompiledModel, conv2d_nchwc_pallas, ops.conv2d_blocked,
           ops.conv2d_block_blocked, ops.conv2d, nn_ops.conv2d,
           nn_ops.conv_block, matmul_pallas, matmul_padded,
           ssd_intra_pallas, flash_attention_pallas]
    for fn in fns:
        default = inspect.signature(fn).parameters["interpret"].default
        assert default is None, f"{fn.__qualname__}: interpret={default}"


def test_manifest_drops_interpret_and_old_key_is_ignored(tmp_path, rng):
    """``interpret`` belongs to the host an artifact loads on, so the
    manifest no longer stores it; a manifest that still has it loads."""
    import json

    from repro.engine import InferenceSession, compile as compile_session

    g, shapes = _tiny_graph()
    sess = compile_session(g, shapes)
    x = jnp.asarray(rng.normal(size=(1, 3, 8, 8)).astype(np.float32))
    want = np.asarray(sess.predict(x))
    art = tmp_path / "art"
    sess.save(art)
    manifest = json.loads((art / "manifest.json").read_text())
    assert "interpret" not in manifest
    manifest["interpret"] = True             # as older builds wrote it
    (art / "manifest.json").write_text(json.dumps(manifest))
    loaded = InferenceSession.load(art)
    assert loaded.interpret is None
    assert np.asarray(loaded.predict(x)).tobytes() == want.tobytes()
