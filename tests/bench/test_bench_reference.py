"""The float32 reference against the program's model at a small size, with
qk-norm and an untied head, and with a tied head."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import _benchpath  # noqa: F401
from benchlib import driver, spec, weights


def _cfg(tied: bool) -> dict:
    return {"name": "tiny", "family": "dense", "hidden_size": 64,
            "num_attention_heads": 4, "num_key_value_heads": 2,
            "head_dim": 16, "intermediate_size": 128, "vocab_size": 256,
            "num_hidden_layers": 3, "tie_word_embeddings": tied,
            "qk_norm": not tied, "rms_norm_eps": 1e-6,
            "rope_theta": 1e6, "dtype": "float32"}


@pytest.mark.parametrize("tied", [False, True])
def test_reference_matches_the_programs_forward(tied):
    from repro.models import transformer
    c = _cfg(tied)
    mc = driver.model_config(c).replace(attn_impl="xla")
    seed = 2**32 + 9
    params = jax.tree.map(lambda a: a.astype(jnp.float32),
                          weights.served_params(c, seed, 3))
    toks = np.random.default_rng(0).integers(0, 256, 45).astype(np.int32)
    with jax.default_matmul_precision("highest"):
        want, _ = transformer.forward(params, mc, jnp.asarray(toks[None]),
                                      training=False)
    want = np.asarray(want[0])
    ref = spec.reference("references/dense.py")
    pos = np.arange(3, 45, 4)
    got = ref.logits_at(c, seed, 3, [toks], [pos], pad_to=16)[0]
    assert np.max(np.abs(got - want[pos])) < 1e-4 * np.max(np.abs(want))


def test_fp8_control_departs_from_the_reference():
    c = _cfg(False)
    ref = spec.reference("references/dense.py")
    toks = np.random.default_rng(1).integers(0, 256, 32).astype(np.int32)
    pos = np.arange(32)
    f32 = ref.logits_at(c, 5, 0, [toks], [pos], pad_to=32)[0]
    fp8 = ref.logits_at(c, 5, 0, [toks], [pos], mode="fp8", pad_to=32)[0]
    assert np.max(np.abs(fp8 - f32)) > 0.05 * np.max(np.abs(f32))
