"""Each plain reference against the program's ``Backbone.apply`` at smoke
size, on the same seeded weights: a float32 program agrees within 2e-4 of
the logits' scale, and a bfloat16 program does not."""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import MUX, TINY_DENSE, tiny_config
from harness import check, spec, weights

# float32 against float32: only the order of summation differs
RTOL = 2e-4
CASES = {"dense": (TINY_DENSE, {"paged": True, "page_size": 16})}


def _setup(reference, dtype, seed=5):
    model, serving = CASES[reference]
    config = tiny_config(f"tiny-{reference}",
                         dict(model, dtype="float32",
                              param_dtype="float32"),
                         reference, serving)
    cfg = spec.model_config(config)
    params = weights.make(cfg, seed)
    run_cfg = dataclasses.replace(cfg, dtype=dtype)
    rng = np.random.default_rng(seed)
    b, n, t = 2, MUX["n"], 12
    tokens = rng.integers(0, cfg.vocab, (b, n, t)).astype(np.int32)
    return config, cfg, run_cfg, params, tokens


def _compare(reference, dtype):
    from repro.models import Backbone
    config, cfg, run_cfg, params, tokens = _setup(reference, dtype)
    b, n, t = tokens.shape
    with jax.default_matmul_precision("highest"):
        prog = np.asarray(Backbone.apply(params, jnp.asarray(tokens),
                                         run_cfg)["logits"], np.float32)
    queries = np.array([(e, s, l) for e in range(b) for s in range(t)
                        for l in range(n)], np.int32)
    ref = check.reference(config).logits(
        config["model"], config["mux"], weights.Weights(cfg, 5),
        tokens.transpose(0, 2, 1), np.ones((b, t, n), bool), queries)
    got = prog[queries[:, 0], queries[:, 2], queries[:, 1]]
    return np.abs(got - ref).max() / np.abs(ref).max()


@pytest.mark.parametrize("reference", sorted(CASES))
def test_reference_matches_float32_program(reference):
    assert _compare(reference, "float32") < RTOL


@pytest.mark.parametrize("reference", sorted(CASES))
def test_bfloat16_program_fails_the_tolerance(reference):
    assert _compare(reference, "bfloat16") > RTOL


def test_one_layer_made_alone_equals_the_whole():
    """The reference makes each layer's weights again from the seed; they
    must be the very values the program was given."""
    config, cfg, _, params, _ = _setup("dense", "float32")
    w = weights.Weights(cfg, 5)
    head, period, groups = cfg.layer_pattern()
    for i in range(cfg.n_layers):
        if i < head:
            want = params["head_layers"][i]
        elif i < head + period * groups:
            g, j = divmod(i - head, period)
            want = jax.tree.map(lambda a: a[g], params["blocks"][j])
        else:
            want = params["tail_layers"][i - head - period * groups]
        jax.tree.map(np.testing.assert_array_equal, w.layer(i), want)
    np.testing.assert_array_equal(w.glob("embed/table"),
                                  params["embed"]["table"])
