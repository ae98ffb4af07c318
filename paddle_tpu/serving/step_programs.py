"""The two step programs of a decode-capable model: what the scheduler
dispatches every iteration, as functions of the model and of nothing else
of the scheduler.

A step program is the model's ``decode_fn`` / ``prefill_chunk_fn`` with the
sampling around it, jitted.  What it is depends on the model, on ``top_k``
(static: ``lax.top_k`` needs a compile-time k) and on whether the cache's
pytree is donated; the width of the decode step and of a chunk are shapes of
its arguments.  ``DecodeModel.step_programs`` builds the pair once a
``(top_k, donate)`` and keeps it, so every scheduler over one model object
dispatches the SAME jitted callable and jax traces a shape once, however many
schedulers, engines or replicas are built over that model.  (A compile is a
device's: replicas on different devices still compile one executable each.)
"""
from __future__ import annotations

__all__ = ["StepPrograms", "sample_token"]


def sample_token(logits, key, temp, top_k):
    """One sampled token id: greedy argmax when ``temp <= 0``, else
    temperature-scaled (optionally top-k-truncated) categorical draw
    with ``key``.  Shape-stable and branch-free (``where``, not
    ``cond``) so greedy and sampling requests share ONE compiled decode
    step — a slot's sampling mode never changes the dispatched shape."""
    import jax
    import jax.numpy as jnp

    greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    z = logits / jnp.maximum(temp, 1e-6)
    if top_k is not None:
        # static k (a DecodeConfig knob): lax.top_k needs a compile-time
        # k, so the menu of sampling truncations is fixed per scheduler
        kth = jax.lax.top_k(z, top_k)[0][..., -1]
        z = jnp.where(z < kth, -jnp.inf, z)
    sampled = jax.random.categorical(key, z).astype(jnp.int32)
    return jnp.where(temp > 0, sampled, greedy)


class StepPrograms:
    """The jitted ``decode`` and ``chunk`` programs of one model for one
    ``(top_k, donate)``, and ``chunk_counts``: whether the chunk program
    returns the model's step counters beside its token.  That is a fact of
    the model's ``prefill_chunk_fn`` that only running it shows, so it is
    None until the chunk program has been traced once — by whichever
    scheduler got there first — and True or False for every holder after.

    Every step takes ``(params, pools, ...)``: the weights as an argument
    that is never donated, the cache's whole pytree donated where ``donate``.
    """

    def __init__(self, model, top_k, donate):
        import jax
        import jax.numpy as jnp

        self.chunk_counts = None
        pools_arg = (1,) if donate else ()

        def decode(params, pools, tokens, positions, tables, kv_lens,
                   seeds, temps, previous, from_previous):
            # a slot that decoded in the step before takes its token
            # from that step's output, still on the device; one whose
            # token the host holds (a prefill's first token, a hand-off,
            # a step already read) takes ``tokens``
            tokens = jnp.where(from_previous, previous[:tokens.shape[0]],
                               tokens)
            logits, pools, *counts = model.decode_fn(
                params, tokens, positions, pools, tables, kv_lens)

            def samp(logit, seed, pos, temp):
                # the carried per-request key, folded with the
                # sampled token's ABSOLUTE position (kv_lens = the
                # new token's index) — identical between continuous
                # batching and solo serving, whatever the slot mix
                k = jax.random.fold_in(jax.random.PRNGKey(seed), pos)
                return sample_token(logit, k, temp, top_k)

            toks = jax.vmap(samp)(logits, seeds, kv_lens, temps)
            if counts:
                # the model's step counters ride the tokens' readback
                toks = jnp.concatenate([toks, counts[0].astype(jnp.int32)])
            return toks, pools

        def chunk(params, pools, tokens, start, valid, chunk_pages,
                  gather_pages, slot, seed, temp):
            logits, pools, *counts = model.prefill_chunk_fn(
                params, tokens, start, valid, pools, chunk_pages,
                gather_pages, slot)
            # the first generated token sits at absolute position
            # start + valid = the prompt's length at the FINAL chunk,
            # the only one whose sample is used: the same logits row
            # and the same key however the prompt was cut, so chunked
            # and monolithic first tokens match bitwise
            kk = jax.random.fold_in(jax.random.PRNGKey(seed), start + valid)
            tok = sample_token(logits, kk, temp, top_k)
            self.chunk_counts = bool(counts)
            if counts:
                # the model's chunk counters ride the token's readback
                tok = jnp.concatenate(
                    [tok[None], counts[0].astype(jnp.int32)])
            return tok, pools

        self.decode = jax.jit(decode, donate_argnums=pools_arg)
        # one callable for every chunk width: the width is ``tokens``' shape
        self.chunk = jax.jit(chunk, donate_argnums=pools_arg)
