"""The gated delta rule with a per-channel decay (Kimi Delta Attention,
arXiv:2510.26692) on a slot-indexed state.

A head keeps ``S [d_k, d_v]`` float32.  One token with key ``k`` and query
``q`` in ``R^{d_k}``, value ``v`` in ``R^{d_v}``, log-decay ``g`` in ``R^{d_k}``
(a VECTOR a head: a rate a key channel) and write strength ``beta``:

    S' = (I - beta k k^T) Diag(exp(g)) S + beta k v^T,    o = S'^T q

The delta rule reads ``k^T Diag(exp(g)) S`` before it can write, so the decode
update is three dependent passes over the state in plain XLA; here it is ONE:
:func:`kda_state_decode` (custom call ``kda_state_decode``) takes a grid step
per (decoding slot, block of heads), reads the block's state once, decays,
corrects, reads out and writes it once, in place (the state is aliased in and
out).  Slots that do not decode are not visited: the grid walks the LIVE slots
(``kv_lens > 0``) in order, and the steps past the last one stay on its block,
which moves nothing.  :func:`kda_chunk` is the chunk-wise form for prefill in
plain ``jax.numpy``: blocks of ``KDA_BLOCK`` tokens, inside a block the WY form
(one unit-lower-triangular solve a block and head) with the cumulative
log-decay kept as DIFFERENCES ``exp(G_i - G_j)``, ``i >= j`` — never
``exp(-G)``, which overflows float32 after a few hundred tokens of a fast
channel.
"""
from __future__ import annotations

import functools
import math

__all__ = ["kda_state_decode", "kda_recurrent_step", "kda_chunk", "KDA_BLOCK",
           "KERNEL_NAME"]

KERNEL_NAME = "kda_state_decode"
KDA_BLOCK = 64              # tokens of one block of the chunk-wise form
_HEADS_A_STEP = 32          # heads of one grid step: 32 x 64 KB of state
_VMEM_LIMIT = 48 * 2 ** 20  # in + out blocks double-buffered are 8 MB; the
                            # unrolled head loop's temporaries come on top


def kda_recurrent_step(state, q, k, v, g, beta, live=None):
    """The update above for every slot and head in plain ``jax.numpy``:
    ``state [S, H, dk, dv]`` float32, ``q, k, g [S, H, dk]``, ``v [S, H, dv]``,
    ``beta [S, H]``; slots where ``live`` is false keep their state and read
    zeros.  Returns ``(o [S, H, dv], state')``."""
    import jax.numpy as jnp

    f32 = jnp.float32
    sd = state * jnp.exp(g.astype(f32))[..., :, None]
    ks = jnp.sum(k.astype(f32)[..., :, None] * sd, axis=-2)
    u = beta.astype(f32)[..., None] * (v.astype(f32) - ks)
    new = sd + k.astype(f32)[..., :, None] * u[..., None, :]
    o = jnp.sum(q.astype(f32)[..., :, None] * new, axis=-2)
    if live is not None:
        new = jnp.where(live[:, None, None, None], new, state)
        o = jnp.where(live[:, None, None], o, 0.0)
    return o, new


def _decode_kernel(slots_ref, n_ref, kvec_ref, v_ref, s_ref, o_ref, out_ref,
                   *, heads):
    """One grid step = ``heads`` heads of one live slot.  ``kvec_ref [dk, 4 *
    heads]``: the key-indexed vectors (exp(g), k, beta k, q; ``heads`` columns
    each) with the key channel on the sublanes, so that a column broadcasts
    over the state's lanes; ``v_ref [heads, dv]`` value rows on the lanes."""
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    i = pl.program_id(0)

    @pl.when(i < n_ref[0])
    def _update():
        for h in range(heads):
            def col(kind, h=h):
                c = kind * heads + h
                return kvec_ref[:, c:c + 1]                      # [dk, 1]

            sd = s_ref[h] * col(0)                               # decayed
            ks = jnp.sum(col(1) * sd, axis=0, keepdims=True)     # [1, dv]
            new = sd + col(2) * (v_ref[h:h + 1, :] - ks)
            out_ref[h] = new
            o_ref[h:h + 1, :] = jnp.sum(col(3) * new, axis=0, keepdims=True)

    # nobody decodes: the one block the grid stands on goes back as it came
    @pl.when((n_ref[0] == 0) & (i == 0) & (pl.program_id(1) == 0))
    def _keep():
        out_ref[...] = s_ref[...]
        o_ref[...] = jnp.zeros_like(o_ref)


def kda_state_decode(state, q, k, v, g, beta, live, *, layer=None, impl=None,
                     interpret=None):
    """One token of the gated delta rule for every slot that decodes.

    state ``[S, H, dk, dv]`` float32, or a stack ``[L, S, H, dk, dv]``
    addressed in place by the static ``layer``; ``q, k, g [S, H, dk]``, ``v
    [S, H, dv]``, ``beta [S, H]`` (any float dtype; computed in float32);
    ``live [S]`` bool.  Returns ``(o [S, H, dv] float32, state')`` with
    ``state'`` the WHOLE argument updated (donate it: the kernel aliases it);
    a slot that is not live keeps its state and reads exact zeros.
    ``impl``: None/"auto" (the Pallas kernel on a TPU, :func:`kda_recurrent_
    step` elsewhere), "reference", "pallas"."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    from .. import observability as obs
    from ..core import cpu_backend

    if impl in (None, "auto"):
        impl = "reference" if cpu_backend() else "pallas"
    if impl not in ("reference", "pallas"):
        raise ValueError("impl must be auto|reference|pallas, got %r" % impl)
    if (state.ndim == 5) != (layer is not None):
        raise ValueError("state is [S, H, dk, dv], or [L, S, H, dk, dv] with "
                         "layer=")
    S, H, dk, dv = state.shape[-4:]
    if state.dtype != jnp.float32:
        raise ValueError("the delta-rule state is float32, got %s"
                         % state.dtype)
    f32 = jnp.float32
    if impl == "reference":
        old = state if layer is None else state[layer]
        o, new = kda_recurrent_step(old, q, k, v, g, beta, live)
        return o, new if layer is None else state.at[layer].set(new)
    if interpret is None:
        interpret = cpu_backend()
    hb = math.gcd(H, _HEADS_A_STEP)
    nj = H // hb
    obs.counter("kda.decode.grid_steps", labels={
        "slots": S, "heads": H, "dk": dk, "dv": dv, "heads_a_step": hb}
    ).inc(S * nj)
    # the live slots first, in order; past them the last live one again
    n_live = live.sum().astype(jnp.int32)
    order = jnp.argsort(~live, stable=True).astype(jnp.int32)
    slots = jnp.where(jnp.arange(S) < n_live, order,
                      order[jnp.maximum(n_live - 1, 0)])
    k32, beta32 = k.astype(f32), beta.astype(f32)[..., None]
    kvec = jnp.stack([jnp.exp(g.astype(f32)), k32, beta32 * k32,
                      q.astype(f32)], axis=1)                    # [S,4,H,dk]
    kvec = kvec.reshape(S, 4, nj, hb, dk).transpose(0, 2, 4, 1, 3).reshape(
        S, nj, dk, 4 * hb)
    lead = () if layer is None else (None,)
    at = () if layer is None else (int(layer),)

    def block(i, j, slots, n):
        # a step past the live slots stands on the last one's last block
        return slots[i], jnp.where(i < n[0], j, nj - 1)

    state_spec = pl.BlockSpec(
        lead + (None, hb, dk, dv),
        lambda i, j, s, n: at + block(i, j, s, n) + (0, 0))
    o, new = pl.pallas_call(
        functools.partial(_decode_kernel, heads=hb),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(S, nj),
            in_specs=[
                pl.BlockSpec((None, None, dk, 4 * hb),
                             lambda i, j, s, n: block(i, j, s, n) + (0, 0)),
                pl.BlockSpec((None, hb, dv),
                             lambda i, j, s, n: block(i, j, s, n) + (0,)),
                state_spec],
            out_specs=[
                pl.BlockSpec((None, hb, dv),
                             lambda i, j, s, n: block(i, j, s, n) + (0,)),
                state_spec]),
        out_shape=[jax.ShapeDtypeStruct((S, H, dv), f32),
                   jax.ShapeDtypeStruct(state.shape, f32)],
        input_output_aliases={4: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
        name=KERNEL_NAME,
    )(slots, n_live.reshape(1), kvec, v.astype(f32), state)
    # the rows of slots the grid never visited were never written
    return jnp.where(live[:, None, None], o, 0.0), new


def kda_chunk(q, k, v, g, beta, state, valid):
    """The recurrence over one sequence's chunk, block by block: ``q, k, g [C,
    H, dk]``, ``v [C, H, dv]``, ``beta [C, H]`` float32, ``state [H, dk, dv]``
    float32 before row 0; rows at or past ``valid`` are padding (no decay, no
    write).  Returns ``(o [C, H, dv], state')`` with ``o_t = S_t^T q_t``.

    Inside a block of ``B`` rows with ``G_i = sum_{t <= i} g_t``: the writes
    ``u_i = beta_i (v_i - (exp(G_i) k_i)^T S_0 - sum_{j < i} A_ij u_j)``, ``A_ij
    = sum_c k_i[c] k_j[c] exp(G_i[c] - G_j[c])``, are one unit-lower-triangular
    solve against ``[beta v | beta exp(G) k]`` (the WY form: ``U = X_v - X_k
    S_0``); then ``o_i = (exp(G_i) q_i)^T S_0 + sum_{j <= i} P_ij u_j`` with
    ``P`` as ``A`` from ``q_i``, and ``S_B = Diag(exp(G_B)) S_0 + sum_j
    (exp(G_B - G_j) k_j) u_j^T``.  Every exponent is ``<= 0``."""
    import jax
    import jax.numpy as jnp
    from jax.scipy.linalg import solve_triangular

    C, H, _ = q.shape
    dv = v.shape[-1]
    B = math.gcd(C, KDA_BLOCK)
    nb = C // B
    hi = jax.lax.Precision.HIGHEST
    real = (jnp.arange(C) < valid)[:, None]
    g = jnp.where(real[..., None], g, 0.0)
    beta = jnp.where(real, beta, 0.0)
    idx = jnp.arange(B)
    lower = idx[:, None] >= idx[None, :]                         # j <= i

    def split(x):                                 # [C, H, ..] -> [nb, H, B, ..]
        return x.reshape((nb, B) + x.shape[1:]).swapaxes(1, 2)

    def step(S, xs):
        qb, kb, vb, gb, bb = xs               # [H, B, dk|dv], beta [H, B]
        G = jnp.cumsum(gb, axis=1)                               # [H, B, dk]
        # exp(G_i - G_j) where j <= i, inside the contraction over channels
        diff = jnp.exp(jnp.where(lower[None, :, :, None],
                                 G[:, :, None, :] - G[:, None, :, :], -1e30))
        a = jnp.sum(kb[:, :, None, :] * kb[:, None, :, :] * diff, axis=-1)
        p = jnp.sum(qb[:, :, None, :] * kb[:, None, :, :] * diff, axis=-1)
        eg = jnp.exp(G)
        unit = jnp.where(idx[:, None] > idx[None, :],
                         bb[:, :, None] * a, 0.0) + jnp.eye(B, dtype=a.dtype)
        rhs = bb[..., None] * jnp.concatenate([vb, eg * kb], axis=-1)
        x = solve_triangular(unit, rhs, lower=True, unit_diagonal=True)
        u = x[..., :dv] - jnp.einsum("hbk,hkv->hbv", x[..., dv:], S,
                                     precision=hi)
        o = (jnp.einsum("hbk,hkv->hbv", eg * qb, S, precision=hi)
             + jnp.einsum("hij,hjv->hiv", p, u, precision=hi))
        tail = jnp.exp(G[:, -1:, :] - G) * kb                    # [H, B, dk]
        S = (eg[:, -1, :, None] * S
             + jnp.einsum("hbk,hbv->hkv", tail, u, precision=hi))
        return S, o

    state, o = jax.lax.scan(
        step, state, tuple(split(x) for x in (q, k, v, g)) + (
            beta.reshape(nb, B, H).swapaxes(1, 2),))
    return o.swapaxes(1, 2).reshape(C, H, dv), state
