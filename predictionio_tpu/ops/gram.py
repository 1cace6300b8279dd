"""The weighted Gramian of the ALS normal equations.

The per-row system build Σ_l w·f fᵀ is where the FLOPs are
(``ALSAlgorithm.scala:75-85`` role): one batched einsum
``[B, L, r] → [B, r, r]`` over the gathered factor rows, accumulated in
float32 whatever the rows' dtype. ``models/als.py::_lhs_fn`` is its one
caller.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def gram_weighted(F: jax.Array, w: jax.Array,
                  bf16: bool = False) -> jax.Array:
    """Baseline batched weighted gram: ``A[..., i, :, :] = Σ_l w·f fᵀ``.
    F: [..., L, r], w: [..., L] → [..., r, r]."""
    if bf16:
        Fw = (F * w[..., None]).astype(jnp.bfloat16)
        Fc = F.astype(jnp.bfloat16)
        return jnp.einsum("...lr,...ls->...rs", Fw, Fc,
                          preferred_element_type=jnp.float32)
    # F may still be a bf16 gather shadow even when the bf16 *compute*
    # mode is off — pin the accumulator wide either way
    return jnp.einsum("...lr,...ls,...l->...rs", F, F, w,
                      preferred_element_type=jnp.float32)
