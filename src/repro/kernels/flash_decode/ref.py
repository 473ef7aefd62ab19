"""Pure-jnp oracle for flash decode: the XLA grouped-query decode over the
layer's slice of the stacked cache."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.models.attention import decode_attention_gqa


def decode_ref(q: jnp.ndarray, k_cache: jnp.ndarray, v_cache: jnp.ndarray,
               layer, lengths) -> jnp.ndarray:
    """q: (B, Hq, Dh); caches: (L, B, Smax, Hkv, Dh); attend row b to
    positions [0, lengths[b]) (``lengths`` () or (B,))."""
    k = jax.lax.dynamic_index_in_dim(k_cache, layer, 0, False)
    v = jax.lax.dynamic_index_in_dim(v_cache, layer, 0, False)
    lens = jnp.broadcast_to(jnp.asarray(lengths), q.shape[:1])
    return decode_attention_gqa(q[:, None], k, v, lens)[:, 0]
