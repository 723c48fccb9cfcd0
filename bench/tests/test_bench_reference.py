"""The plain reference: the operation counts the published figures give,
and the same logits as the program's plain NCHW plan on the CPU."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import reference as R
from families import cnn


@pytest.mark.parametrize("arch,gmacs", [
    # torchvision's model tables: resnet50 4.09 GFLOPS, densenet121 2.83
    # (counted as multiply-accumulates of convolutions and the dense layer)
    ("resnet50", 4.09), ("densenet121", 2.83)])
def test_operation_counts_match_the_published_figures(arch, gmacs):
    assert R.count_macs(arch, 224, 1000) / 1e9 == pytest.approx(gmacs,
                                                                 rel=5e-3)


@pytest.mark.parametrize("arch,layers", [("resnet50", (53, 53, 1)),
                                         ("densenet121", (120, 121, 1))])
def test_layer_counts(arch, layers):
    kinds = [k for k, _ in R.param_spec(arch, 1000)]
    assert (kinds.count("conv"), kinds.count("bn"),
            kinds.count("dense")) == layers


@pytest.mark.parametrize("model,arch", [("resnet-50", "resnet50"),
                                        ("densenet-121", "densenet121")])
def test_reference_matches_the_programs_nchw_plan(model, arch):
    from repro.core.pipeline import Pipeline
    from repro.engine import compile_model

    image, classes = 32, 1000
    key = cnn.seed_key(11)
    params = cnn.make_params(arch, classes, key)
    graph, shapes = cnn.program_graph(model, image)
    shapes = {k: (2,) + v[1:] for k, v in shapes.items()}
    prog = compile_model(Pipeline.preset("nchw").run(graph, shapes),
                         cnn.to_program(graph, params))
    x = cnn.make_images(key, 2, image)
    want = np.asarray(jax.jit(R.forward, static_argnums=0)(
        arch, params, jnp.asarray(x)))
    got = np.asarray(prog.predict(jnp.asarray(x)))
    # float32 on both sides, summed in other orders
    assert cnn.logit_gap(got, want).max() < 1e-4
    # and the answers differ from image to image far beyond that
    assert cnn.logit_gap(want[::-1], want).max() > 0.05


@pytest.mark.parametrize("arch", ["resnet50", "densenet121"])
def test_three_pass_control_is_far_from_the_reference(arch):
    image = 32
    key = cnn.seed_key(12)
    params = cnn.make_params(arch, 1000, key)
    x = jnp.asarray(cnn.make_images(key, 4, image))
    fwd = jax.jit(R.forward, static_argnames=("arch", "precision"))
    ref = np.asarray(fwd(arch=arch, params=params, x=x))
    ctl = np.asarray(fwd(arch=arch, params=params, x=x, precision="high"))
    assert cnn.logit_gap(ctl, ref).max() > 3e-6


def test_a_graph_that_is_not_the_reference_is_refused():
    params = cnn.make_params("resnet50", 1000, cnn.seed_key(0))
    graph, _ = cnn.program_graph("densenet-121", 32)
    with pytest.raises(ValueError):
        cnn.to_program(graph, params)
    graph, _ = cnn.program_graph("resnet-101", 32)
    with pytest.raises(ValueError):
        cnn.to_program(graph, params)
