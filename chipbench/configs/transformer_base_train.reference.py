"""Plain reference for ``transformer_base_train``: scaled dot-product
attention (Vaswani et al. 2017, eq. 1) in straightforward float32
``jax.numpy`` at the highest matmul precision, with the causal and the
key-length masks the program's kernel takes.  No kernel, no blocking.

A whole-model float32 reference (forward, loss and gradients of the
encoder-decoder) is not in the tree yet: PERF.md lists it under Open
questions.  Until then the run is held to this attention, to the loss a
uniform prediction gives (ln vocab) and to a falling loss.
"""
import jax
import jax.numpy as jnp


def attention(q, k, v, causal=False, kv_lens=None):
    """``q`` [B,H,T,D], ``k``/``v`` [B,H,S,D] -> [B,H,T,D]; keys at positions
    >= ``kv_lens[b]`` are masked, and with ``causal`` keys after the query."""
    with jax.default_matmul_precision("highest"):
        q, k, v = (x.astype(jnp.float32) for x in (q, k, v))
        T, S, D = q.shape[2], k.shape[2], q.shape[3]
        s = jnp.einsum("bhtd,bhsd->bhts", q, k) / jnp.sqrt(jnp.float32(D))
        mask = jnp.ones((1, 1, T, S), bool)
        if causal:
            mask = mask & (jnp.arange(S)[None, :] <= jnp.arange(T)[:, None])
        if kv_lens is not None:
            mask = mask & (jnp.arange(S)[None, None, None, :]
                           < kv_lens[:, None, None, None])
        s = jnp.where(mask, s, -1e30)
        p = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("bhts,bhsd->bhtd", p, v)
