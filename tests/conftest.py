import os
import sys

# tests see the single host device (the dry-run sets its own XLA_FLAGS in a
# separate process — never here)
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import jax
import jax.numpy as jnp
import numpy as np
import pytest


@pytest.fixture(scope="session")
def rng():
    return np.random.default_rng(0)


def make_lm_batch(cfg, B=2, S=32, seed=1):
    key = jax.random.PRNGKey(seed)
    ks = jax.random.split(key, 4)
    batch = {
        "tokens": jax.random.randint(ks[0], (B, S), 0, cfg.vocab_size),
        "targets": jax.random.randint(ks[1], (B, S), 0, cfg.vocab_size),
        "loss_mask": jnp.ones((B, S), jnp.float32),
    }
    if cfg.frontend == "vision_stub":
        n_patch = 8
        batch = {
            "tokens": batch["tokens"][:, n_patch:],
            "targets": batch["targets"][:, n_patch:],
            "loss_mask": batch["loss_mask"][:, n_patch:],
            "patch_embed": jax.random.normal(ks[2], (B, n_patch, cfg.d_model), jnp.float32),
        }
    if cfg.enc_dec:
        batch["frames"] = jax.random.normal(
            ks[3], (B, cfg.enc_seq_len, cfg.d_model), jnp.float32
        )
    return batch


def f32(cfg):
    """Reduced config in float32 for tight numeric comparisons."""
    return cfg.replace(dtype="float32", param_dtype="float32")
