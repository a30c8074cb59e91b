"""What ``tests/test_latent.py`` and ``tests/test_latent_engine.py`` share:
Kanana-2 at test size (``LlamaConfig.kanana_tiny``) with the published keys it
is mapped from, and the benchmark's seeded parameters with the answers of the
reference's expanded form."""

import jax
import pytest

from ray_tpu.models.llama import LlamaConfig, forward, init_params

CFG = LlamaConfig.kanana_tiny()

T = 44
TOL = dict(atol=5e-5, rtol=1e-4)


@pytest.fixture(scope="module")
def model():
    """(params, tokens [2, T], ``forward``'s logits)."""
    params = init_params(jax.random.PRNGKey(7), CFG)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, T), 0, CFG.vocab_size)
    return params, tokens, forward(params, tokens, CFG)
