"""Plain reference for ``transformer_base_lm``: the decoder-only LM's forward
pass over one whole sequence in straightforward float32 ``jax.numpy`` at the
highest matmul precision.  No kernel, no cache, no paging, no batching.

Follows the published block (Vaswani et al. 2017, section 3: post-LayerNorm
residual blocks, ReLU feed-forward, sinusoid positions, embeddings scaled by
sqrt(d_model)); departures, all the served model's own: no encoder and so no
cross-attention (an LM), no bias on the attention projections, an untied
output head.  The parameters are an ARGUMENT, so the jitted executable holds
no weights and fits the compile cache.
"""
import jax
import jax.numpy as jnp


def _layer_norm(x, scale, bias, eps=1e-5):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * scale + bias


def next_token_logits(params, tokens, length, n_head):
    """Logits [V] for the token after ``tokens[:length]``; ``tokens`` [T] is
    padded to a fixed length (the pad tail is masked by causality)."""
    with jax.default_matmul_precision("highest"):
        T = tokens.shape[0]
        d_model = params["tok_emb"].shape[1]
        dh = d_model // n_head
        x = (params["tok_emb"][tokens] * jnp.sqrt(jnp.float32(d_model))
             + params["pos_table"][:T])
        causal = jnp.arange(T)[None, :] <= jnp.arange(T)[:, None]
        for lp in params["layers"]:
            q, k, v = ((x @ lp[w]).reshape(T, n_head, dh).transpose(1, 0, 2)
                       for w in ("wq", "wk", "wv"))
            s = jnp.einsum("htd,hsd->hts", q, k) / jnp.sqrt(jnp.float32(dh))
            p = jax.nn.softmax(jnp.where(causal[None], s, -1e30), axis=-1)
            ctx = jnp.einsum("hts,hsd->htd", p, v).transpose(1, 0, 2)
            x = _layer_norm(x + ctx.reshape(T, d_model) @ lp["wo"],
                            lp["ln1_s"], lp["ln1_b"])
            h = jnp.maximum(x @ lp["ffn_w1"] + lp["ffn_b1"], 0.0)
            x = _layer_norm(x + h @ lp["ffn_w2"] + lp["ffn_b2"],
                            lp["ln2_s"], lp["ln2_b"])
        last = jax.lax.dynamic_index_in_dim(x, length - 1, axis=0,
                                            keepdims=False)
        return last @ params["out_w"]


def paged_decode(q, k_pool, v_pool, page_tables, kv_lens):
    """One query per sequence against its paged keys and values: ``q``
    [S,H,D], pools [P,ps,H,D], ``page_tables`` [S,MP]; keys at or after
    ``kv_lens[s]`` are masked and a sequence with none gives zeros."""
    with jax.default_matmul_precision("highest"):
        S, H, D = q.shape
        k = k_pool[page_tables].reshape(S, -1, H, D).astype(jnp.float32)
        v = v_pool[page_tables].reshape(S, -1, H, D).astype(jnp.float32)
        s = jnp.einsum("shd,snhd->shn", q.astype(jnp.float32), k) / jnp.sqrt(
            jnp.float32(D))
        live = jnp.arange(k.shape[1])[None, None, :] < kv_lens[:, None, None]
        p = jax.nn.softmax(jnp.where(live, s, -1e30), axis=-1)
        out = jnp.einsum("shn,snhd->shd", jnp.where(live, p, 0.0), v)
        return jnp.where(kv_lens[:, None, None] > 0, out, 0.0)


def paged_prefill(q, k_pool, v_pool, pages, start):
    """A chunk of one sequence's queries at absolute positions ``start ..``
    against that sequence's paged keys and values, causally: ``q`` [C,H,D],
    ``pages`` [MP]."""
    with jax.default_matmul_precision("highest"):
        C, H, D = q.shape
        k = k_pool[pages].reshape(-1, H, D).astype(jnp.float32)
        v = v_pool[pages].reshape(-1, H, D).astype(jnp.float32)
        s = jnp.einsum("chd,nhd->hcn", q.astype(jnp.float32), k) / jnp.sqrt(
            jnp.float32(D))
        seen = jnp.arange(k.shape[0])[None, :] <= (start + jnp.arange(C))[:, None]
        p = jax.nn.softmax(jnp.where(seen[None], s, -1e30), axis=-1)
        return jnp.einsum("hcn,nhd->chd", p, v)
