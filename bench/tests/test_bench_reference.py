"""The plain reference (bench/reference/qwen_dense.py) agrees with the
program's own float32 forward pass on the benchmark's weights, at tiny
sizes of both families; its weights follow the program's layout; the
float8 control moves the logits by far more than rounding."""
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import fleet
from bench.tests.helpers import DATA


def members():
    out = {}
    for f in ("tiny-fleet", "tiny-edge"):
        cfg = json.loads((DATA / "configs" / f"{f}.json").read_text())
        out.update(fleet.members_of(cfg))
    return out


@pytest.mark.parametrize("name", ["tiny-cloud", "tiny-edge-a"])
def test_reference_matches_program_forward(name):
    from repro.models import transformer
    m = members()[name]
    ref = fleet.spec_lib.load_module("reference", "qwen_dense")
    params = fleet.make_params(ref, m, seed=2 ** 31 + 99)
    # the benchmark's weights take the program's layout and dtypes
    shapes = jax.eval_shape(lambda k: transformer.init_params(m.cfg, k),
                            jax.random.PRNGKey(0))
    assert jax.tree.structure(params) == jax.tree.structure(shapes)
    assert all(a.shape == b.shape and a.dtype == b.dtype for a, b in
               zip(jax.tree.leaves(params), jax.tree.leaves(shapes)))
    toks = jnp.asarray(np.random.default_rng(0).integers(1, 256, 40),
                       jnp.int32)
    cfg32 = m.cfg.with_(dtype="float32", use_pallas=False)
    with jax.default_matmul_precision("highest"):
        prog, _ = transformer.forward(cfg32, params, toks[None])
    mine = ref.reference_logits(m.hf, params, toks)
    np.testing.assert_allclose(np.asarray(mine), np.asarray(prog[0]),
                               atol=2e-4, rtol=2e-4)
    low = ref.reference_logits(m.hf, params, toks, control=True)
    assert float(jnp.max(jnp.abs(low - mine))) > 100 * float(
        jnp.max(jnp.abs(np.asarray(prog[0]) - mine)))


def test_weights_come_from_the_seed():
    m = members()["tiny-edge-a"]
    ref = fleet.spec_lib.load_module("reference", "qwen_dense")
    a = jax.tree.leaves(fleet.make_params(ref, m, 5))
    b = jax.tree.leaves(fleet.make_params(ref, m, 5))
    c = jax.tree.leaves(fleet.make_params(ref, m, 6))
    assert all(bool(jnp.all(x == y)) for x, y in zip(a, b))
    assert not all(bool(jnp.all(x == y)) for x, y in zip(a, c))
