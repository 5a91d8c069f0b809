"""Model zoo tests: shapes, dtypes, determinism across families."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mlops_tpu.config import ModelConfig
from mlops_tpu.models import FAMILIES, build_model, init_params
from mlops_tpu.schema import NUM_CATEGORICAL, NUM_NUMERIC


# what a family has to be told beside the shared sizes: `exaone_moe` and
# `nemotron_h` have no list of their own among `ModelConfig`'s defaults
# (`layer_types` is `lfm2_moe`'s published list, which they refuse)
FAMILY_FIELDS = {
    "exaone_moe": {"layer_types": ("sliding_attention", "full_attention"), "attn_window": 16},
    "nemotron_h": {
        "layer_types": ("moe", "mamba"), "ssm_dim": 64, "ssm_heads": 8, "ssm_state": 16,
        "ssm_groups": 2, "moe_ffn_dim": 32,
    },
}


def _dummy_batch(n=16, seed=0):
    rng = np.random.default_rng(seed)
    cat = rng.integers(0, 2, size=(n, NUM_CATEGORICAL)).astype(np.int32)
    num = rng.normal(size=(n, NUM_NUMERIC)).astype(np.float32)
    return jnp.asarray(cat), jnp.asarray(num)


@pytest.mark.parametrize("family", FAMILIES)
def test_forward_shapes(family):
    config = ModelConfig(
        family=family, hidden_dims=(32, 32), token_dim=32, depth=2, heads=4,
        **FAMILY_FIELDS.get(family, {}),
    )
    model = build_model(config)
    variables = init_params(model, jax.random.PRNGKey(0))
    cat, num = _dummy_batch()
    logits = model.apply(variables, cat, num, train=False)
    assert logits.shape == (16,)
    assert logits.dtype == jnp.float32
    assert bool(jnp.isfinite(logits).all())


@pytest.mark.parametrize("family", FAMILIES)
def test_forward_deterministic_eval(family):
    config = ModelConfig(
        family=family, hidden_dims=(32,), token_dim=32, depth=1, heads=4,
        **FAMILY_FIELDS.get(family, {}),
    )
    model = build_model(config)
    variables = init_params(model, jax.random.PRNGKey(1))
    cat, num = _dummy_batch()
    a = model.apply(variables, cat, num, train=False)
    b = model.apply(variables, cat, num, train=False)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_params_are_float32():
    model = build_model(ModelConfig(family="mlp", hidden_dims=(32,)))
    variables = init_params(model, jax.random.PRNGKey(0))
    for leaf in jax.tree_util.tree_leaves(variables["params"]):
        assert leaf.dtype == jnp.float32


def test_dropout_needs_rng_only_in_train():
    model = build_model(ModelConfig(family="mlp", hidden_dims=(32, 32), dropout=0.5))
    variables = init_params(model, jax.random.PRNGKey(0))
    cat, num = _dummy_batch()
    out1 = model.apply(
        variables, cat, num, train=True, rngs={"dropout": jax.random.PRNGKey(2)}
    )
    out2 = model.apply(
        variables, cat, num, train=True, rngs={"dropout": jax.random.PRNGKey(3)}
    )
    assert not np.allclose(np.asarray(out1), np.asarray(out2))


class TestDeepEnsemble:
    """Vmapped deep ensemble (models/ensemble.py) — the MXU-native answer
    to the reference's RandomForest variance reduction
    (`01-train-model.ipynb:195-227`)."""

    def _build(self, k=4):
        config = ModelConfig(family="mlp", ensemble_size=k, hidden_dims=(32, 32))
        model = build_model(config)
        variables = init_params(model, jax.random.PRNGKey(0))
        return model, variables

    def test_train_mode_exposes_member_axis(self):
        model, variables = self._build(k=4)
        cat, num = _dummy_batch()
        logits = model.apply(
            variables, cat, num, train=True, rngs={"dropout": jax.random.PRNGKey(1)}
        )
        assert logits.shape == (4, 16)

    def test_eval_mode_keeps_zoo_contract(self):
        model, variables = self._build(k=4)
        cat, num = _dummy_batch()
        logits = model.apply(variables, cat, num, train=False)
        assert logits.shape == (16,)
        assert logits.dtype == jnp.float32

    def test_members_are_independently_initialized(self):
        model, variables = self._build(k=4)
        leaf = jax.tree_util.tree_leaves(variables["params"])[0]
        assert leaf.shape[0] == 4
        # split params rngs: members must not be clones of one another
        flat = np.asarray(leaf).reshape(4, -1)
        assert not np.allclose(flat[0], flat[1])

    def test_eval_is_logit_of_mean_member_probability(self):
        model, variables = self._build(k=4)
        cat, num = _dummy_batch()
        agg = model.apply(variables, cat, num, train=False)
        # dropout off in train=False; reconstruct member logits by slicing
        # each member's params out and running the bare member module
        member_cfg = ModelConfig(family="mlp", ensemble_size=1, hidden_dims=(32, 32))
        member = build_model(member_cfg)
        probs = []
        for i in range(4):
            member_params = jax.tree.map(lambda x: x[i], variables["params"]["member"])
            lg = member.apply({"params": member_params}, cat, num, train=False)
            probs.append(jax.nn.sigmoid(lg))
        mean_prob = jnp.stack(probs).mean(0)
        np.testing.assert_allclose(
            np.asarray(jax.nn.sigmoid(agg)), np.asarray(mean_prob), atol=1e-5
        )

    def test_ensemble_size_one_is_not_wrapped(self):
        config = ModelConfig(family="mlp", ensemble_size=1, hidden_dims=(32,))
        from mlops_tpu.models import MLP

        assert isinstance(build_model(config), MLP)
