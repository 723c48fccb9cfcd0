"""Plain NCHW references of the benchmark's networks.

Straightforward ``lax`` convolutions over NCHW arrays, written from the
published architectures; nothing here comes from the program under test.
Parameters are one flat list in the order the layers are defined: a conv
is ``(w,)`` with ``w`` in OIHW, a batch norm in inference form is
``(scale, shift)`` (``x * scale + shift``, as a served network holds it),
a dense layer is ``(w, b)`` with ``w`` of shape ``(in, out)``.

``forward(arch, params, x)`` computes in float32, the convolutions and
the dense layer at "highest" precision: the reference.  With
``precision="high"`` every convolution and the dense layer take three
passes over bfloat16 parts instead, ``hi*hi + hi*lo + lo*hi`` of operands
split as ``a = hi + lo`` (what a TPU runs at "high" precision), written
out so that it computes the same on any backend: the control that the
correctness limit has to fail.
"""
from __future__ import annotations

from typing import Callable, Dict, List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

HIGHEST = lax.Precision.HIGHEST


class Ops:
    """The operations the networks are written in.  Each layer with
    parameters takes the next entry of ``params``."""

    def __init__(self, params: Sequence[Tuple[jnp.ndarray, ...]]):
        self._params = iter(params)

    def _take(self, shapes):
        p = next(self._params)
        if tuple(a.shape for a in p) != tuple(shapes):
            raise ValueError(f"parameter shapes {[a.shape for a in p]} != "
                             f"{list(shapes)}")
        return [a.astype(jnp.float32) for a in p]

    @staticmethod
    def product(f, x, w):
        """``f(x, w)``, bilinear, as a convolution or the dense layer
        computes it."""
        return f(x, w)

    def conv(self, x, cout: int, k: int, stride: int = 1, pad: int = 0):
        (w,) = self._take([(cout, x.shape[1], k, k)])
        return self.product(lambda a, b: lax.conv_general_dilated(
            a, b, (stride, stride), [(pad, pad), (pad, pad)],
            dimension_numbers=("NCHW", "OIHW", "NCHW"), precision=HIGHEST),
            x, w)

    def bn(self, x):
        c = x.shape[1]
        scale, shift = self._take([(c,), (c,)])
        return x * scale[None, :, None, None] + shift[None, :, None, None]

    def dense(self, x, units: int):
        w, b = self._take([(x.shape[1], units), (units,)])
        return self.product(
            lambda a, c: jnp.dot(a, c, precision=HIGHEST), x, w) + b

    @staticmethod
    def relu(x):
        return jnp.maximum(x, 0)

    @staticmethod
    def max_pool(x, k: int, stride: int, pad: int):
        return lax.reduce_window(
            x, jnp.array(-jnp.inf, x.dtype), lax.max, (1, 1, k, k),
            (1, 1, stride, stride), [(0, 0), (0, 0), (pad, pad), (pad, pad)])

    @staticmethod
    def avg_pool(x, k: int):
        s = lax.reduce_window(x, jnp.array(0, x.dtype), lax.add,
                              (1, 1, k, k), (1, 1, k, k), "VALID")
        return s / (k * k)

    @staticmethod
    def global_avg_pool(x):
        return x.mean(axis=(2, 3))


def resnet50(o: Ops, x, classes: int):
    """ResNet-50 (He et al. 2016, Table 1 "50-layer") in torchvision's
    form: bottleneck units of 1x1, 3x3 (carrying the stride) and 1x1
    convs, each followed by batch norm, with a projected shortcut (1x1
    conv and batch norm) where the shape changes."""
    x = o.max_pool(o.relu(o.bn(o.conv(x, 64, 7, 2, 3))), 3, 2, 1)
    for stage, (units, width) in enumerate(
            zip((3, 4, 6, 3), (256, 512, 1024, 2048))):
        for u in range(units):
            stride = 2 if stage > 0 and u == 0 else 1
            y = o.relu(o.bn(o.conv(x, width // 4, 1)))
            y = o.relu(o.bn(o.conv(y, width // 4, 3, stride, 1)))
            y = o.bn(o.conv(y, width, 1))
            if stride != 1 or x.shape[1] != width:
                x = o.bn(o.conv(x, width, 1, stride))
            x = o.relu(y + x)
    return o.dense(o.global_avg_pool(x), classes)


def densenet121(o: Ops, x, classes: int):
    """DenseNet-121 (Huang et al. 2017, Table 1): growth rate 32, dense
    blocks of (6, 12, 24, 16) layers of BN-ReLU-conv1x1(128) and
    BN-ReLU-conv3x3(32) whose output is concatenated after their input;
    transitions of BN-ReLU-conv1x1 to half the channels and 2x2 average
    pooling; a final BN-ReLU before global pooling."""
    growth = 32
    x = o.max_pool(o.relu(o.bn(o.conv(x, 2 * growth, 7, 2, 3))), 3, 2, 1)
    for block, layers in enumerate((6, 12, 24, 16)):
        for _ in range(layers):
            y = o.conv(o.relu(o.bn(x)), 4 * growth, 1)
            y = o.conv(o.relu(o.bn(y)), growth, 3, 1, 1)
            x = jnp.concatenate([x, y], axis=1)
        if block != 3:
            x = o.avg_pool(o.conv(o.relu(o.bn(x)), x.shape[1] // 2, 1), 2)
    return o.dense(o.global_avg_pool(o.relu(o.bn(x))), classes)


def _bf16(a):
    return a.astype(jnp.bfloat16).astype(jnp.float32)


class ThreePassOps(Ops):
    """Every product in three passes over bfloat16 parts."""

    @staticmethod
    def product(f, x, w):
        xh, wh = _bf16(x), _bf16(w)
        xl, wl = _bf16(x - xh), _bf16(w - wh)
        return f(xh, wh) + f(xh, wl) + f(xl, wh)


PRECISIONS = {"highest": Ops, "high": ThreePassOps}

ARCHS: Dict[str, Callable] = {"resnet50": resnet50,
                              "densenet121": densenet121}

Spec = List[Tuple[str, List[Tuple[int, ...]]]]


def param_spec(arch: str, classes: int) -> Spec:
    """``arch``'s parameters as (kind, shapes) in the order ``forward``
    takes them, read off one abstract pass (parameter shapes do not
    depend on the image size)."""
    spec: Spec = []

    class _Record(Ops):
        def __init__(self):
            super().__init__(())

        def _take(self, shapes):
            kind = {1: "conv", 2: "bn" if len(shapes[0]) == 1 else "dense"}
            spec.append((kind[len(shapes)], [tuple(s) for s in shapes]))
            return [jnp.zeros(s, jnp.float32) for s in shapes]

    jax.eval_shape(lambda x: ARCHS[arch](_Record(), x, classes),
                   jax.ShapeDtypeStruct((1, 3, 64, 64), jnp.float32))
    return spec


def forward(arch: str, params, x, classes: int = 1000,
            precision: str = "highest"):
    """Logits of ``arch`` for the NCHW batch ``x``."""
    return ARCHS[arch](PRECISIONS[precision](params),
                       x.astype(jnp.float32), classes)


def count_macs(arch: str, image: int, classes: int) -> int:
    """Multiply-accumulates of one ``image`` x ``image`` input through
    ``arch``'s convolutions and dense layer, from the shapes of its
    operations: what the network needs, whatever a program computes."""
    spec = param_spec(arch, classes)
    params = [tuple(jax.ShapeDtypeStruct(s, jnp.float32) for s in shapes)
              for _, shapes in spec]
    x = jax.ShapeDtypeStruct((1, 3, image, image), jnp.float32)
    jaxpr = jax.make_jaxpr(lambda p, x: forward(arch, p, x, classes=classes)
                           )(params, x)
    macs = 0
    for eqn in jaxpr.jaxpr.eqns:
        if eqn.primitive.name == "conv_general_dilated":
            lhs, rhs = (v.aval.shape for v in eqn.invars)
            out = eqn.outvars[0].aval.shape
            # OIHW kernel: every output element takes I*H*W products
            macs += int(np.prod(out)) * int(np.prod(rhs[1:]))
        elif eqn.primitive.name == "dot_general":
            lhs, rhs = (v.aval.shape for v in eqn.invars)
            macs += int(np.prod(lhs)) * int(rhs[-1])
    return macs
