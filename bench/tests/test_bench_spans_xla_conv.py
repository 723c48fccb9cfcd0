"""The compiler's-convolution lowering (``xla_conv``) nests a scope of its
own inside the graph node's; ``spans.scope_of`` still gives the node."""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import spans as S
from repro.core.layout import kernel_to_kcrs_ck, to_nchwc
from repro.kernels.ops import conv2d_block_jnp


@pytest.mark.parametrize("op_name,node", [
    ("jit(forward)/conv1/xla_conv/convolution", "conv1"),
    ("jit(forward)/jit(main)/conv1/xla_conv/conv_general_dilated", "conv1"),
])
def test_scope_of_xla_conv(op_name, node):
    assert S.scope_of(op_name) == node


def test_lowered_xla_conv_ops_belong_to_their_node():
    rng = np.random.default_rng(0)
    x = to_nchwc(jnp.asarray(rng.normal(size=(1, 3, 16, 16)),
                             jnp.float32), 3)
    w = kernel_to_kcrs_ck(jnp.asarray(rng.normal(size=(8, 3, 7, 7)),
                                      jnp.float32), 3, 8)

    def forward(x, w):
        with jax.named_scope("conv1"):
            return conv2d_block_jnp(x, w, stride=2, pad=3, relu=True,
                                    variant="xla_conv")

    hlo = jax.jit(forward).lower(x, w).compile().as_text()
    names = {n for n in re.findall(r'op_name="([^"]+)"', hlo)
             if "/xla_conv/" in n}
    assert names
    assert {S.scope_of(n) for n in names} == {"conv1"}
