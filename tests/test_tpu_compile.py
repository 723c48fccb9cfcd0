"""Compile-only checks for one described v5e chip: the main-path kernels
at ResNet-50 / LM widths, and the ResNet-50 224 whole-graph forward.

Nothing runs: ``jax.jit(...).lower(shapes).compile()`` raises what the
TPU compiler would raise on the chip (an unsupported lowering, a block
past the VMEM limit, a program past HBM).  The topology is described
inside a fixture, never at import: only one process at a time may load
the TPU library, and every xdist worker imports this file.
"""
import pytest

import jax
import jax.numpy as jnp

from repro.core.epilogue import EpilogueSpec, PoolSpec
from repro.core.schedule import ConvSchedule
from repro.kernels.conv2d_nchwc import conv2d_nchwc_pallas
from repro.kernels.flash_attention import flash_attention_pallas
from repro.kernels.ops import conv2d_block_jnp
from repro.kernels.matmul_blocked import MatmulSchedule, matmul_padded


@pytest.fixture(scope="module")
def topo():
    import os

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:      # noqa: BLE001 — any failure to describe
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache off here
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", enabled)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def shape_of(topo):
    """``shape_of(shape, dtype)``: an argument placed on one chip."""
    from jax.sharding import SingleDeviceSharding

    one_chip = SingleDeviceSharding(topo.devices[0])

    def make(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(tuple(shape), dtype, sharding=one_chip)

    return make


def _compile(fn, *args):
    return jax.jit(fn).lower(*args).compile()


# (name, N, C_in, C_out, H, K, stride, pad, schedule the planner picks,
#  pool, residual) — ResNet-50 at 224 as the planner lays it out
CONV_CASES = {
    "stem_7x7_s2_maxpool": (1, 3, 64, 224, 7, 2, 3,
                            ConvSchedule(3, 64, 2, 112, True, "tap_stack"),
                            PoolSpec("max", 3, 2, 1), False),
    "56_3x3": (1, 64, 64, 56, 3, 1, 1,
               ConvSchedule(64, 64, 4, 4, False, "per_tap"), None, False),
    "14_3x3_residual": (1, 256, 256, 14, 3, 1, 1,
                        ConvSchedule(128, 256, 2, 1, True, "per_tap"),
                        None, True),
}


@pytest.mark.parametrize("case", sorted(CONV_CASES))
def test_conv2d_nchwc_pallas_compiles(shape_of, case):
    n, cin, cout, h, k, stride, pad, sched, pool, residual = CONV_CASES[case]
    ic, oc = sched.ic_bn, sched.oc_bn
    hp = h + 2 * pad
    oh = (hp - k) // stride + 1
    x = shape_of((n, cin // ic, hp, hp, ic))
    w = shape_of((cout // oc, cin // ic, k, k, ic, oc))
    shift = shape_of((cout // oc, oc))
    res = shape_of((n, cout // oc, oh, oh, oc)) if residual else None
    spec = EpilogueSpec(relu=True, pool=pool)

    def conv(x, w, shift, res):
        return conv2d_nchwc_pallas(x, w, None, shift, res, stride=stride,
                                   schedule=sched, epilogue=spec,
                                   interpret=False)

    compiled = _compile(conv, x, w, shift, res)
    assert "tpu_custom_call" in compiled.as_text()
    out_hw = pool.out_hw(oh, oh) if pool is not None else (oh, oh)
    assert compiled.out_info.shape == (n, cout // oc) + out_hw + (oc,)


def test_stem_xla_conv_bytes_accessed(shape_of):
    """The ResNet-50 stem at batch 8 and 224 on the jnp path, as the
    planner lowers it (``xla_conv``, ic_bn 1, oc_bn 64), with shift, ReLU
    and the fused 3x3/2 max-pool at "highest": XLA's cost analysis reads
    under 2 GB accessed.  The tap_stack lowering, whose 49 tap copies pad
    3 channels to 128 lanes, reads 8.2 GB."""
    spec = EpilogueSpec(relu=True, pool=PoolSpec("max", 3, 2, 1))

    def stem(x, w, shift):
        return conv2d_block_jnp(x, w, None, shift, stride=2, pad=3,
                                epilogue=spec, variant="xla_conv")

    with jax.default_matmul_precision("highest"):
        compiled = _compile(stem, shape_of((8, 3, 224, 224, 1)),
                            shape_of((1, 3, 7, 7, 1, 64)), shape_of((1, 64)))
    cost = compiled.cost_analysis()
    cost = cost[0] if isinstance(cost, list) else cost
    assert compiled.out_info.shape == (8, 1, 56, 56, 64)
    assert cost["bytes accessed"] < 2e9


def test_matmul_padded_compiles(shape_of):
    def mm(a, b):
        return matmul_padded(a, b, schedule=MatmulSchedule(), interpret=False)

    compiled = _compile(mm, shape_of((1024, 1024)), shape_of((1024, 1024)))
    assert "tpu_custom_call" in compiled.as_text()


def test_flash_attention_pallas_compiles(shape_of):
    # qwen2-1.5b: 12 query heads over 2 KV heads of width 128, 1024 tokens
    q = shape_of((1, 12, 1024, 128))
    kv = shape_of((1, 2, 1024, 128))

    def attn(q, k, v):
        return flash_attention_pallas(q, k, v, causal=True, interpret=False)

    compiled = _compile(attn, q, kv, kv)
    assert "tpu_custom_call" in compiled.as_text()


def test_resnet50_forward_compiles(shape_of):
    """The default serving program: ResNet-50 at 224, batch 1, jnp
    templates, whole-graph dispatch — as ``compile()`` plans it."""
    from repro.engine import compile as compile_session

    model = compile_session("resnet-50", (1, 3, 224, 224)).specialize(1)
    params = jax.tree.map(lambda a: shape_of(a.shape, a.dtype), model.params)
    x = {model.input_name: shape_of((1, 3, 224, 224))}
    compiled = model._forward.lower(params, x).compile()
    mem = compiled.memory_analysis()
    assert compiled.out_info.shape == (1, 1000)
    # weights (~100 MB fp32) plus activations fit one 16 GiB chip
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 2**30


def test_starcoder2_decode_compiles(shape_of):
    """The served decode step of StarCoder2-3B at its published widths,
    as ``LMSession`` builds it: the bfloat16 weights (6.06 GB) and a
    4160-position cache fit one chip, and the ops carry the step's
    ``lm.decode`` scope."""
    from repro.configs import ARCHS
    from repro.engine import LMSession
    from repro.models.lm import init_cache, init_params

    cfg = ARCHS["starcoder2-3b"]

    def place(tree):
        return jax.tree.map(lambda a: shape_of(a.shape, a.dtype), tree)

    params = place(jax.eval_shape(lambda k: init_params(cfg, k),
                                  jax.random.PRNGKey(0)))
    cache = place(jax.eval_shape(lambda: init_cache(cfg, 1, 4160)))
    sess = LMSession(cfg, params, max_len=4160)
    compiled = sess._decode().lower(
        params, shape_of((1, 1), jnp.int32), cache,
        shape_of((), jnp.int32)).compile()
    mem = compiled.memory_analysis()
    assert 6.0e9 < mem.argument_size_in_bytes < 6.4e9
    assert (mem.argument_size_in_bytes + mem.temp_size_in_bytes
            + mem.output_size_in_bytes) < 16e9
    assert "lm.decode" in compiled.as_text()
