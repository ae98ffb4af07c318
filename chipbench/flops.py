"""Operations a model's algorithm needs, counted from shapes.

Model FLOPs in the sense of model-FLOP utilization: forward plus backward,
one multiply-add = 2 FLOPs, recomputation (the flash backward recomputes
the scores) NOT counted, the masked half of a causal attention NOT counted.
Embedding look-ups, layer norms, softmax and the optimizer are left out, as is
usual: they are bandwidth, not MXU work.
"""


def transformer_matmul_params(cfg):
    """Parameters that multiply a token: projections, FFN, output head."""
    d, di, v = cfg["d_model"], cfg["d_inner"], cfg["vocab"]
    attn, ffn = 4 * d * d, 2 * d * di
    enc = cfg["n_layer"] * (attn + ffn)
    dec = cfg["n_layer"] * (2 * attn + ffn)      # self + cross attention
    return {"encoder": enc, "decoder": dec, "head": d * v}


def attention_flops(batch, q_len, kv_len, d_model, causal, backward=True):
    """QK^T and PV: 2 matmuls x 2 FLOPs x B x T x S x d_model forward; the
    backward needs twice the forward (dQ, dK, dV, dP)."""
    fwd = 4.0 * batch * q_len * kv_len * d_model * (0.5 if causal else 1.0)
    return fwd * (3.0 if backward else 1.0)


def transformer_train_step(cfg, batch, seq):
    """Encoder-decoder Transformer, one optimizer step on ``batch`` pairs of
    ``seq`` source and ``seq`` target tokens: 6 x parameters x tokens for the
    matmuls (encoder parameters see the source tokens, decoder and head the
    target tokens), plus 6 full + 6 causal + 6 cross attentions."""
    p = transformer_matmul_params(cfg)
    tokens = batch * seq
    matmul = 6.0 * tokens * (p["encoder"] + p["decoder"] + p["head"])
    full = attention_flops(batch, seq, seq, cfg["d_model"], causal=False)
    causal = attention_flops(batch, seq, seq, cfg["d_model"], causal=True)
    attn = cfg["n_layer"] * (2 * full + causal)
    return {"matmul": matmul, "attention": attn, "total": matmul + attn}
