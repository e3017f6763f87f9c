"""The plain reference and its control, at a size the CPU holds.

The reference imports nothing of the program; here, and only here, the
program's own oracle stands as a second witness that the two describe the
same network.  The control (bf16_3x, the step below the configuration's
``float32, highest``) has to fail the limit the check holds the program
to.
"""
import json

import numpy as np
import pytest
from conftest import BENCH

import reference


def _config(name):
    return json.loads((BENCH / ("tests" if name == "tiny" else "configs")
                       / f"{name}.json").read_text())


def _head(name, n):
    """A configuration's first ``n`` layers with the residual joins among
    them, at its widths, and its limit."""
    cfg = _config(name)
    skips = [e for e in cfg["skip_edges"] if e[1] < n]
    return cfg["layers"][:n], skips, cfg["check"]["max_rel_err"]


@pytest.mark.parametrize("name", ["tiny", "resnet50", "mobilenet_v3"])
def test_reference_matches_the_programs_oracle(name):
    """The whole network at batch 1, requests made from the configured
    image, against the program's own XLA oracle: both read the file's
    shapes, adapter and joins alike."""
    import jax.numpy as jnp
    from repro.api import execute_network_reference, from_layers
    from repro.core.dataflow import ConvWorkload

    cfg = _config(name)
    layers = cfg["layers"]
    n = 2 if name == "tiny" else 1
    w = reference.init_weights(layers, seed=3)
    x = reference.make_inputs(layers, seed=3, n=n, image=cfg.get("input"))
    ours = reference.forward(layers, cfg["skip_edges"], jnp.asarray(x), w)
    graph = from_layers(
        [ConvWorkload(N=n, M=lay["M"], C=lay["C"], P=lay["P"], Q=lay["Q"],
                      R=lay["R"], S=lay["S"], stride=lay["stride"],
                      name=lay["name"]) for lay in layers],
        name=name, skip_edges=[tuple(e) for e in cfg["skip_edges"]])
    theirs = execute_network_reference(graph, x, w)
    assert reference.rel_err(np.asarray(ours), np.asarray(theirs)) < 1e-5


def test_requests_are_images_padded_to_the_canvas():
    cfg = _config("resnet50")
    x = reference.make_inputs(cfg["layers"], seed=1, n=1, image=cfg["input"])
    assert x.shape == (1, 229, 229, 3)
    # SAME padding of 224 for a 7x7 stride-2 conv: 2 rows before, 3 after
    assert not x[0, :2].any() and not x[0, -3:].any()
    assert x[0, 2].any() and x[0, -4].any()


def test_reference_in_blocks_equals_one_pass():
    cfg = _config("tiny")
    layers = cfg["layers"]
    w = reference.init_weights(layers, seed=4)
    x = reference.make_inputs(layers, seed=4, n=5)
    whole = reference.reference_outputs(layers, cfg["skip_edges"], x, w, 8)
    blocks = reference.reference_outputs(layers, cfg["skip_edges"], x, w, 2)
    np.testing.assert_array_equal(whole, blocks)


@pytest.mark.parametrize("name", ["resnet50", "mobilenet_v3"])
def test_control_fails_the_limit(name):
    layers, skips, limit = _head(name, 4)
    w = reference.init_weights(layers, seed=5)
    x = reference.make_inputs(layers, seed=5, n=2)
    want = reference.reference_outputs(layers, skips, x, w, 2)
    got = reference.reference_outputs(layers, skips, x, w, 2,
                                      precision="bf16_3x")
    errs = [reference.rel_err(g, r) for g, r in zip(got, want)]
    assert max(errs) > limit, (errs, limit)


def test_seeds_beyond_32_bits_differ():
    layers = _config("tiny")["layers"]
    a = reference.make_inputs(layers, seed=5, n=1)
    b = reference.make_inputs(layers, seed=5 + 2 ** 32, n=1)
    assert not np.array_equal(a, b)
    np.testing.assert_array_equal(a, reference.make_inputs(layers, 5, 1))


def test_rel_err_refuses_non_finite_and_misshapen_outputs():
    want = np.ones((2, 2))
    assert reference.rel_err(np.full((2, 2), np.nan), want) == float("inf")
    assert reference.rel_err(np.ones((2, 3)), want) == float("inf")
    assert reference.rel_err(want * 1.5, want) == 0.5
