"""One key per seed for the program and the reference alike.

``jax.random.PRNGKey`` keeps only the low 32 bits of a larger seed, so
the high bits are folded in: seeds up to 2**64 give distinct keys."""
from __future__ import annotations

import jax


def model_key(seed: int) -> jax.Array:
    if seed < 0 or seed >= 2 ** 64:
        raise ValueError(f"seed {seed} is not in [0, 2**64)")
    return jax.random.fold_in(jax.random.PRNGKey(seed % 2 ** 32),
                              seed // 2 ** 32)
