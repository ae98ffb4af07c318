"""What the flash test files share: inputs, the two entries of the training
kernel, comparisons.  The files are cut along the kernel families (training
forward and chosen tiles; backward engines; the rows entry; ring attention;
the plain paged walk; the prefill twins; the grouped walk; the listed walk) so
that ``--dist loadfile`` can place each on a worker of its own."""
import numpy as np
import jax
import jax.numpy as jnp

from paddle_tpu.parallel import flash_attention as FA
from paddle_tpu.parallel.flash_attention import (
    flash_attention,
    flash_attention_rows,
)


def _rand_qkv(B=2, H=2, T=64, D=16, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    q = jax.random.normal(ks[0], (B, H, T, D), jnp.float32)
    k = jax.random.normal(ks[1], (B, H, T, D), jnp.float32)
    v = jax.random.normal(ks[2], (B, H, T, D), jnp.float32)
    return q, k, v


def _force_bwd(monkeypatch, engine):
    """The backward is chosen from the shape and from nothing else; a test
    that needs one engine at a toy shape replaces the chooser."""
    monkeypatch.setattr(FA, "_bwd_engine", lambda *a, **kw: engine)


def _through_rows(q, k, v, **kw):
    """``flash_attention_rows`` on ``[B, H, T, D]`` data: the heads folded
    into the rows' lanes by the TEST, so what runs is the rows entry alone."""
    H = q.shape[1]
    out = flash_attention_rows(FA._to_rows(q), FA._to_rows(k), FA._to_rows(v),
                               n_head=H, **kw)
    return FA._from_rows(out, H)


_ENTRIES = {"bhtd": flash_attention, "rows": _through_rows}


def _out_and_grads(attn, q, k, v, w, **kw):
    """``attn``'s output and the gradients of ``sum(out * w)`` in q, k, v."""
    def f(q, k, v):
        out = attn(q, k, v, **kw)
        return jnp.sum(out.astype(jnp.float32) * w), out
    (_, out), grads = jax.value_and_grad(f, argnums=(0, 1, 2), has_aux=True)(q, k, v)
    return (out,) + grads


def _assert_out_and_grads_close(got, want):
    np.testing.assert_allclose(np.asarray(got[0]), np.asarray(want[0]), rtol=2e-4, atol=2e-4)
    for a, b in zip(got[1:], want[1:]):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-3, atol=2e-3)


def _rand_qkvw(B, H, T, S, D, seed):
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    return (jax.random.normal(ks[0], (B, H, T, D), jnp.float32),
            jax.random.normal(ks[1], (B, H, S, D), jnp.float32),
            jax.random.normal(ks[2], (B, H, S, D), jnp.float32),
            jax.random.normal(ks[3], (B, H, T, D), jnp.float32))


def _small_chooser(monkeypatch, vmem_budget=None):
    """The choosers at toy widths: blocks of at most 16 rows in the forward
    and in the backward (and, with a small budget, only part of S resident a
    forward step), so the interpret-mode shapes below walk the same forms the
    cells' shapes do: several query blocks x several key blocks a head."""
    monkeypatch.setattr(FA, "_FWD_BLOCK", 16)
    monkeypatch.setattr(FA, "_BWD_BLOCK_Q", 16)
    monkeypatch.setattr(FA, "_BWD_BLOCK_K", 16)
    if vmem_budget is not None:
        monkeypatch.setattr(FA, "_FWD_VMEM_BUDGET", vmem_budget)


_CHOSEN_LENS = {"full": None, "ragged": lambda S: [S, S // 2 + 1, 3],
                "zero-row": lambda S: [S - 5, 0, S]}
