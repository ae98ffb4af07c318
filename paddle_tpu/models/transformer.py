"""Transformer (base) for WMT en-de machine translation.

Reference: python/paddle/fluid/tests/unittests/transformer_model.py and the
fluid Transformer benchmark (test_parallel_executor_transformer.py,
dist_transformer.py).  Same network — post-norm Transformer-base:
n_layer=6, d_model=512, n_head=8, d_inner=2048, sinusoid position encoding,
label smoothing 0.1, Adam + noam LR decay — rebuilt TPU-first:

- Static padded [batch, seq_len] token layout; attention masks are computed
  in-graph from the pad id (no LoD, no host-side bias tensors to feed).
- Every projection is an MXU matmul (fc with num_flatten_dims=2); the whole
  step traces to ONE XLA computation, so residual/bias/softmax/dropout all
  fuse — there is no per-op kernel dispatch to amortize.
- bf16-friendly: softmax/log_softmax run in f32 inside the op lowerings.
"""
from __future__ import annotations

import numpy as np

from .. import layers, nets  # noqa: F401
from .. import optimizer as optim
from ..initializer import NumpyArrayInitializer
from ..param_attr import ParamAttr

# Transformer-base hyperparameters (reference transformer_model.py / the
# ModelHyperParams in dist_transformer.py)
D_MODEL = 512
D_INNER = 2048
N_HEAD = 8
N_LAYER = 6
DROPOUT = 0.1
MAX_LENGTH = 256
SRC_VOCAB = 10000
TRG_VOCAB = 10000
PAD_IDX = 0
EOS_IDX = 1
BOS_IDX = 2


def _position_encoding_table(max_len, d_model):
    """Sinusoid table (reference transformer_model.py position_encoding_init)."""
    pos = np.arange(max_len, dtype=np.float64)[:, None]
    inv = 1.0 / np.power(10000.0, (np.arange(d_model) // 2 * 2.0) / d_model)
    ang = pos * inv[None, :]
    table = np.zeros((max_len, d_model), dtype=np.float32)
    table[:, 0::2] = np.sin(ang[:, 0::2])
    table[:, 1::2] = np.cos(ang[:, 1::2])
    return table


def _causal_bias_table(max_len):
    """[max_len, max_len] upper-triangular -1e9 mask, sliced per sequence."""
    return np.triu(np.full((max_len, max_len), -1e9, dtype=np.float32), k=1)


def _const_table(name, array):
    """A frozen lookup table materialized as a non-trainable parameter; XLA
    const-folds the slice of it into the attention fusion."""
    return layers.create_parameter(
        shape=list(array.shape),
        dtype="float32",
        name=name,
        attr=ParamAttr(
            name=name, initializer=NumpyArrayInitializer(array), trainable=False
        ),
    )


def multi_head_attention(
    queries,
    keys,
    values,
    attn_bias,
    d_key,
    d_value,
    d_model,
    n_head,
    dropout_rate=0.0,
    cache=None,
    use_flash=False,
    flash_causal=False,
    kv_lens=None,
):
    """Reference transformer_model.py:45 multi_head_attention.  [B,T,D] in,
    [B,T,D] out.  With ``use_flash`` (and no ``cache``) the flash kernels read
    the projections' own ``[B, T, H * d]`` rows and write the output
    projection's: no reshape and no transpose on that path (around a Mosaic
    kernel a transpose is a real pass over the tensor in HBM, 180 of them a
    training step before PR 43).  The matmul-softmax path splits heads via
    reshape+transpose, which XLA folds into the matmuls' layouts.
    ``cache`` (dict with 'k','v' variables) enables incremental decode."""
    keys = queries if keys is None else keys
    values = keys if values is None else values

    q = layers.fc(input=queries, size=d_key * n_head, num_flatten_dims=2, bias_attr=False)
    k = layers.fc(input=keys, size=d_key * n_head, num_flatten_dims=2, bias_attr=False)
    v = layers.fc(input=values, size=d_value * n_head, num_flatten_dims=2, bias_attr=False)

    if use_flash and cache is None:
        # fused pallas kernel: padding via kv_lens, no [T,S] bias tensor
        ctx = layers.flash_attention(q, k, v, kv_lens=kv_lens, causal=flash_causal,
                                     n_head=n_head)
        return layers.fc(input=ctx, size=d_model, num_flatten_dims=2, bias_attr=False)

    def split_heads(x, d):
        b, t = x.shape[0], x.shape[1]
        x = layers.reshape(x=x, shape=[b if b and b > 0 else -1, t, n_head, d])
        return layers.transpose(x=x, perm=[0, 2, 1, 3])  # [B,H,T,d]

    q = split_heads(q, d_key)
    k = split_heads(k, d_key)
    v = split_heads(v, d_value)

    if cache is not None:
        k = cache["k"] = layers.concat([cache["k"], k], axis=2)
        v = cache["v"] = layers.concat([cache["v"], v], axis=2)

    product = layers.matmul(x=q, y=k, transpose_y=True, alpha=d_key**-0.5)
    if attn_bias is not None:
        product = layers.elementwise_add(x=product, y=attn_bias)
    weights = layers.softmax(product)
    if dropout_rate:
        weights = layers.dropout(weights, dropout_prob=dropout_rate, is_test=False)
    ctx = layers.matmul(weights, v)  # [B,H,Tq,dv]
    ctx = layers.transpose(ctx, perm=[0, 2, 1, 3])
    b, t = queries.shape[0], queries.shape[1]
    ctx = layers.reshape(x=ctx, shape=[b if b and b > 0 else -1, t, n_head * d_value])
    return layers.fc(input=ctx, size=d_model, num_flatten_dims=2, bias_attr=False)


def positionwise_feed_forward(x, d_inner_hid, d_hid, dropout_rate=0.0):
    """Reference transformer_model.py:167 — two MXU matmuls with fused relu."""
    hidden = layers.fc(input=x, size=d_inner_hid, num_flatten_dims=2, act="relu")
    if dropout_rate:
        hidden = layers.dropout(hidden, dropout_prob=dropout_rate, is_test=False)
    return layers.fc(input=hidden, size=d_hid, num_flatten_dims=2)


def post_process(prev_out, out, dropout_rate=0.0):
    """Residual add + layer_norm (post-norm, as the reference's
    post_process_layer cmd='dan': dropout, add, norm)."""
    if dropout_rate:
        out = layers.dropout(out, dropout_prob=dropout_rate, is_test=False)
    if prev_out is not None:
        out = layers.elementwise_add(x=out, y=prev_out)
    return layers.layer_norm(out, begin_norm_axis=len(out.shape) - 1)


def prepare_encoder_decoder(
    word_ids, vocab_size, d_model, max_length, dropout_rate, pos_table, word_emb_name
):
    """Token embedding * sqrt(d_model) + sinusoid position encoding
    (reference transformer_model.py:185 prepare_encoder)."""
    emb = layers.embedding(
        input=word_ids,
        size=[vocab_size, d_model],
        padding_idx=PAD_IDX,
        param_attr=ParamAttr(name=word_emb_name),
    )
    emb = layers.scale(x=emb, scale=d_model**0.5)
    seq_len = word_ids.shape[1]
    pos_enc = layers.slice(pos_table, axes=[0], starts=[0], ends=[seq_len])
    out = layers.elementwise_add(x=emb, y=pos_enc, axis=1)
    if dropout_rate:
        out = layers.dropout(out, dropout_prob=dropout_rate, is_test=False)
    return out


def _make_pipe(n_layer, stages, microbatches, repeats, use_flash, what):
    """Shared guard-and-construct for the pipelined encoder/decoder stacks."""
    if n_layer % stages:
        raise ValueError("%s n_layer %d %% pipeline_stages %d != 0"
                         % (what, n_layer, stages))
    if use_flash:
        raise ValueError(
            "use_flash composes with sp, not pp: the flash kernel's "
            "sequence-parallel path reads the mesh, which inside a "
            "pipeline stage would nest shard_maps")
    return layers.Pipeline(
        num_stages=stages,
        num_microbatches=microbatches or 2 * stages,
        circular_repeats=repeats)


def encoder_layer(x, attn_bias, n_head, d_key, d_value, d_model, d_inner, dropout,
                  use_flash=False, kv_lens=None):
    attn = multi_head_attention(x, None, None, attn_bias, d_key, d_value, d_model, n_head, dropout,
                                use_flash=use_flash, kv_lens=kv_lens)
    x = post_process(x, attn, dropout)
    ffn = positionwise_feed_forward(x, d_inner, d_model, dropout)
    return post_process(x, ffn, dropout)


def decoder_layer(
    x, enc_out, slf_bias, dec_enc_bias, n_head, d_key, d_value, d_model, d_inner, dropout, cache=None,
    use_flash=False, trg_lens=None, src_lens=None,
):
    slf = multi_head_attention(x, None, None, slf_bias, d_key, d_value, d_model, n_head, dropout, cache=cache,
                               use_flash=use_flash, flash_causal=True, kv_lens=trg_lens)
    x = post_process(x, slf, dropout)
    cross = multi_head_attention(x, enc_out, None, dec_enc_bias, d_key, d_value, d_model, n_head, dropout,
                                 use_flash=use_flash, kv_lens=src_lens)
    x = post_process(x, cross, dropout)
    ffn = positionwise_feed_forward(x, d_inner, d_model, dropout)
    return post_process(x, ffn, dropout)


def _pad_bias(word_ids):
    """[B,1,1,T] additive bias: -1e9 at pad positions, computed in-graph."""
    pad = layers.fill_constant(shape=[1], dtype=word_ids.dtype, value=PAD_IDX)
    is_pad = layers.cast(layers.equal(word_ids, pad), "float32")
    bias = layers.scale(x=is_pad, scale=-1e9)
    return layers.unsqueeze(bias, axes=[1, 2])


def _word_lens(word_ids):
    """[B] int32 non-pad lengths (padding is contiguous at the tail)."""
    pad = layers.fill_constant(shape=[1], dtype=word_ids.dtype, value=PAD_IDX)
    non_pad = layers.cast(layers.logical_not(layers.equal(word_ids, pad)), "float32")
    lens = layers.reduce_sum(non_pad, dim=1)
    lens = layers.cast(lens, "int32")
    lens.stop_gradient = True
    return lens


def wrap_encoder(
    src_word,
    src_vocab_size=SRC_VOCAB,
    max_length=MAX_LENGTH,
    n_layer=N_LAYER,
    n_head=N_HEAD,
    d_model=D_MODEL,
    d_inner=D_INNER,
    dropout=DROPOUT,
    use_flash=False,
    pipeline_stages=0,
    pipeline_microbatches=None,
    pipeline_circular_repeats=1,
):
    """``pipeline_stages=S`` builds the encoder stack as a layers.Pipeline
    (n_layer/S layers per stage, stage-stacked params): under
    ``ParallelExecutor(mesh_shape={"pp": S})`` the stack runs GPipe-style
    with one stage per device; on one device it runs the identical
    microbatched sequence.  The pad bias rides along as a per-microbatch
    side input.  ``pipeline_circular_repeats=R`` (must divide S; the mesh
    then carries S/R pp devices and microbatches come in multiples of
    S/R) opts into the interleaved circular schedule — R stage slices per
    device, bubble (S/R - 1)/(M*R + S/R - 1)."""
    pos_table = _const_table("src_pos_enc_table", _position_encoding_table(max_length, d_model))
    src_bias = _pad_bias(src_word)
    src_lens = _word_lens(src_word) if use_flash else None
    x = prepare_encoder_decoder(src_word, src_vocab_size, d_model, max_length, dropout, pos_table, "src_word_emb")
    if pipeline_stages:
        pipe = _make_pipe(n_layer, pipeline_stages, pipeline_microbatches,
                          pipeline_circular_repeats, use_flash, "encoder")
        with pipe.stage():
            h = pipe.stage_input(x)
            bias_l = pipe.stage_side_input(src_bias)
            for _ in range(n_layer // pipeline_stages):
                h = encoder_layer(h, bias_l, n_head, d_model // n_head,
                                  d_model // n_head, d_model, d_inner, dropout)
            pipe.stage_output(h)
        return pipe(), src_bias
    for _ in range(n_layer):
        x = encoder_layer(x, src_bias, n_head, d_model // n_head, d_model // n_head, d_model, d_inner, dropout,
                          use_flash=use_flash, kv_lens=src_lens)
    return x, src_bias


def wrap_decoder(
    trg_word,
    enc_out,
    src_bias,
    trg_vocab_size=TRG_VOCAB,
    max_length=MAX_LENGTH,
    n_layer=N_LAYER,
    n_head=N_HEAD,
    d_model=D_MODEL,
    d_inner=D_INNER,
    dropout=DROPOUT,
    caches=None,
    causal=True,
    use_flash=False,
    src_word=None,
    pipeline_stages=0,
    pipeline_microbatches=None,
    pipeline_circular_repeats=1,
):
    """``pipeline_stages`` pipelines the decoder stack like wrap_encoder's
    (training graph only — incremental decode with ``caches`` keeps the
    sequential stack): enc_out and both attention biases ride as
    per-microbatch side inputs."""
    pos_table = _const_table("trg_pos_enc_table", _position_encoding_table(max_length, d_model))
    seq_len = trg_word.shape[1]
    trg_lens = _word_lens(trg_word) if use_flash else None
    src_lens = _word_lens(src_word) if (use_flash and src_word is not None) else None
    slf_bias = _pad_bias(trg_word)  # [B,1,1,T]
    if causal:
        causal_table = _const_table("causal_bias_table", _causal_bias_table(max_length))
        causal_bias = layers.slice(causal_table, axes=[0, 1], starts=[0, 0], ends=[seq_len, seq_len])
        causal_bias = layers.unsqueeze(causal_bias, axes=[0, 1])  # [1,1,T,T]
        slf_bias = layers.elementwise_add(x=causal_bias, y=slf_bias)
    x = prepare_encoder_decoder(trg_word, trg_vocab_size, d_model, max_length, dropout, pos_table, "trg_word_emb")
    if pipeline_stages and caches is None:
        pipe = _make_pipe(n_layer, pipeline_stages, pipeline_microbatches,
                          pipeline_circular_repeats, use_flash, "decoder")
        with pipe.stage():
            h = pipe.stage_input(x)
            enc_l = pipe.stage_side_input(enc_out)
            # [B,1,T,T] at runtime (causal [1,1,T,T] broadcast over the
            # [B,1,1,T] pad bias): batch-leading, slices per microbatch
            slf_l = pipe.stage_side_input(slf_bias)
            src_l = pipe.stage_side_input(src_bias)
            for _ in range(n_layer // pipeline_stages):
                h = decoder_layer(
                    h, enc_l, slf_l, src_l, n_head, d_model // n_head,
                    d_model // n_head, d_model, d_inner, dropout)
            pipe.stage_output(h)
        x = pipe()
    else:
        for i in range(n_layer):
            x = decoder_layer(
                x,
                enc_out,
                slf_bias,
                src_bias,
                n_head,
                d_model // n_head,
                d_model // n_head,
                d_model,
                d_inner,
                dropout,
                cache=caches[i] if caches is not None else None,
                use_flash=use_flash and caches is None and causal,
                trg_lens=trg_lens,
                src_lens=src_lens,
            )
    logits = layers.fc(input=x, size=trg_vocab_size, num_flatten_dims=2, bias_attr=False)
    return logits


def transformer(
    src_word,
    trg_word,
    lbl_word,
    src_vocab_size=SRC_VOCAB,
    trg_vocab_size=TRG_VOCAB,
    max_length=MAX_LENGTH,
    n_layer=N_LAYER,
    n_head=N_HEAD,
    d_model=D_MODEL,
    d_inner=D_INNER,
    dropout=DROPOUT,
    label_smooth_eps=0.1,
    use_flash=False,
    pipeline_stages=0,
    pipeline_microbatches=None,
    pipeline_circular_repeats=1,
):
    """Training graph (reference transformer_model.py:282 transformer).
    Returns (avg_cost, sum_cost, token_count, logits).  ``pipeline_stages``
    pipelines BOTH the encoder and decoder stacks (wrap_encoder /
    wrap_decoder) — two stage-stacked parameter sets."""
    enc_out, src_bias = wrap_encoder(src_word, src_vocab_size, max_length, n_layer, n_head, d_model, d_inner, dropout,
                                     use_flash=use_flash, pipeline_stages=pipeline_stages,
                                     pipeline_microbatches=pipeline_microbatches,
                                     pipeline_circular_repeats=pipeline_circular_repeats)
    logits = wrap_decoder(trg_word, enc_out, src_bias, trg_vocab_size, max_length, n_layer, n_head, d_model, d_inner,
                          dropout, use_flash=use_flash, src_word=src_word,
                          pipeline_stages=pipeline_stages,
                          pipeline_microbatches=pipeline_microbatches,
                          pipeline_circular_repeats=pipeline_circular_repeats)

    label = layers.one_hot(input=lbl_word, depth=trg_vocab_size)
    if label_smooth_eps:
        label = layers.label_smooth(label=label, epsilon=label_smooth_eps)
    cost = layers.softmax_with_cross_entropy(logits=logits, label=label, soft_label=True)  # [B,T,1]

    pad = layers.fill_constant(shape=[1], dtype=lbl_word.dtype, value=PAD_IDX)
    non_pad = layers.cast(layers.logical_not(layers.equal(lbl_word, pad)), "float32")
    weights = layers.unsqueeze(non_pad, axes=[2])
    weighted = layers.elementwise_mul(x=cost, y=weights)
    sum_cost = layers.reduce_sum(weighted)
    token_num = layers.reduce_sum(weights)
    token_num.stop_gradient = True
    avg_cost = layers.elementwise_div(x=sum_cost, y=token_num)
    return avg_cost, sum_cost, token_num, logits


def get_model(
    batch_size=32,
    seq_len=64,
    src_vocab_size=SRC_VOCAB,
    trg_vocab_size=TRG_VOCAB,
    max_length=MAX_LENGTH,
    n_layer=N_LAYER,
    n_head=N_HEAD,
    d_model=D_MODEL,
    d_inner=D_INNER,
    dropout=DROPOUT,
    learning_rate=2.0,
    warmup_steps=8000,
    use_flash=False,
    pipeline_stages=0,
    pipeline_microbatches=None,
    pipeline_circular_repeats=1,
):
    import paddle_tpu as fluid

    main = fluid.Program()
    startup = fluid.Program()
    with fluid.program_guard(main, startup):
        src_word = layers.data(name="src_word", shape=[seq_len], dtype="int64")
        trg_word = layers.data(name="trg_word", shape=[seq_len], dtype="int64")
        lbl_word = layers.data(name="lbl_word", shape=[seq_len], dtype="int64")
        avg_cost, sum_cost, token_num, logits = transformer(
            src_word, trg_word, lbl_word,
            src_vocab_size, trg_vocab_size, max_length,
            n_layer, n_head, d_model, d_inner, dropout,
            use_flash=use_flash,
            pipeline_stages=pipeline_stages,
            pipeline_microbatches=pipeline_microbatches,
            pipeline_circular_repeats=pipeline_circular_repeats,
        )
        inference_program = main.clone(for_test=True)
        lr = layers.scale(x=layers.noam_decay(d_model, warmup_steps), scale=float(learning_rate))
        opt = optim.AdamOptimizer(learning_rate=lr, beta1=0.9, beta2=0.98, epsilon=1e-9)
        opt.minimize(avg_cost)
    return {
        "main": main,
        "startup": startup,
        "test": inference_program,
        "feeds": ["src_word", "trg_word", "lbl_word"],
        "loss": avg_cost,
        "sum_cost": sum_cost,
        "token_num": token_num,
        "predict": logits,
    }


def fast_decode(
    src_word,
    beam_size,
    max_out_len,
    src_vocab_size=SRC_VOCAB,
    trg_vocab_size=TRG_VOCAB,
    max_length=MAX_LENGTH,
    n_layer=N_LAYER,
    n_head=N_HEAD,
    d_model=D_MODEL,
    d_inner=D_INNER,
):
    """Beam-search inference graph (reference analog: the transformer
    benchmark's fast_decoder).  TPU-native design: beam lanes fold into the
    batch axis and each While step re-runs the decoder on the *whole padded
    prefix* with causal masking — identical static shapes every iteration,
    so the loop body is one cached XLA computation.  (The reference's
    growing k/v caches are dynamic-shaped; a fixed-size cache decode is a
    later optimization — this path trades FLOPs for compile-once.)

    Build INSIDE the same unique_name scope as the training graph clone so
    parameter names line up with the trained scope.
    """
    import paddle_tpu as fluid

    enc_out, src_bias = wrap_encoder(src_word, src_vocab_size, max_length, n_layer, n_head, d_model, d_inner, 0.0)

    def expand_to_beam(x):
        ex = layers.expand(layers.unsqueeze(x, axes=[1]), [1, beam_size] + [1] * (len(x.shape) - 1))
        return layers.reshape(x=ex, shape=[-1] + [int(d) for d in x.shape[1:]])

    enc_out_b = expand_to_beam(enc_out)          # [B*beam, Ts, D]
    src_bias_b = expand_to_beam(src_bias)        # [B*beam, 1, 1, Ts]

    batch_ref = layers.reduce_sum(enc_out, dim=[1, 2], keep_dim=True)  # [B,1,1] batch-size anchor
    batch_ref = layers.reshape(batch_ref, shape=[-1, 1])

    # decoded tokens so far, padded: [B*beam, max_out_len], starts all PAD
    # with BOS at position 0
    tokens0 = layers.fill_constant_batch_size_like(
        input=enc_out_b, shape=[-1, max_out_len], dtype="int64", value=float(PAD_IDX)
    )
    pos_onehot0 = layers.cast(
        layers.equal(
            layers.cumsum(
                layers.fill_constant_batch_size_like(
                    input=enc_out_b, shape=[-1, max_out_len], dtype="float32", value=1.0
                ),
                axis=1,
            ),
            layers.fill_constant(shape=[1], dtype="float32", value=1.0),
        ),
        "int64",
    )  # one-hot at column 0
    tokens0 = layers.elementwise_add(
        tokens0, layers.scale(pos_onehot0, scale=float(BOS_IDX))
    )
    tokens = layers.assign(tokens0)

    init_ids = layers.fill_constant_batch_size_like(
        input=batch_ref, shape=[-1, beam_size], dtype="int64", value=float(BOS_IDX)
    )
    lane = layers.cumsum(
        layers.fill_constant_batch_size_like(
            input=batch_ref, shape=[-1, beam_size], dtype="float32", value=1.0
        ),
        axis=1,
    )
    one = layers.fill_constant(shape=[1], dtype="float32", value=1.0)
    init_scores = layers.scale(
        x=layers.cast(layers.logical_not(layers.equal(lane, one)), "float32"), scale=-1e9
    )
    pre_ids = layers.assign(init_ids)
    pre_scores = layers.assign(init_scores)

    ids_arr = layers.create_array("int64", capacity=max_out_len)
    scores_arr = layers.create_array("float32", capacity=max_out_len)
    parents_arr = layers.create_array("int32", capacity=max_out_len)

    counter = layers.zeros(shape=[1], dtype="int64", force_cpu=True)
    max_len_const = layers.fill_constant(shape=[1], dtype="int64", value=max_out_len - 1)
    cond = layers.less_than(x=counter, y=max_len_const)

    row_base = layers.scale(
        x=layers.cumsum(
            layers.fill_constant_batch_size_like(
                input=batch_ref, shape=[-1, 1], dtype="float32", value=1.0
            ),
            axis=0,
        ),
        scale=float(beam_size), bias=-float(beam_size),
    )

    while_op = layers.While(cond=cond, maxlen=max_out_len)
    with while_op.block():
        # full-prefix decoder pass with causal mask; positions > counter are
        # PAD so their keys are masked out by the decoder's pad bias
        logits = wrap_decoder(
            tokens, enc_out_b, src_bias_b, trg_vocab_size, max_length,
            n_layer, n_head, d_model, d_inner, 0.0, causal=True,
        )  # [B*beam, max_out_len, V]

        # logits at the current position: one-hot(counter) row-reduce
        step_f = layers.cast(counter, "float32")
        col = layers.cumsum(
            layers.fill_constant_batch_size_like(
                input=enc_out_b, shape=[-1, max_out_len], dtype="float32", value=1.0
            ),
            axis=1,
        )  # 1..L
        onehot = layers.cast(
            layers.equal(col, layers.elementwise_add(step_f, one)), "float32"
        )  # [B*beam, L], 1 at column == counter
        cur_logits = layers.reduce_sum(
            layers.elementwise_mul(logits, layers.unsqueeze(onehot, axes=[2]), axis=0),
            dim=1,
        )  # [B*beam, V]
        probs = layers.softmax(cur_logits)

        topk_scores, topk_ids = layers.topk(probs, k=beam_size)
        topk_scores = layers.reshape(x=topk_scores, shape=[-1, beam_size, beam_size])
        topk_ids = layers.reshape(x=topk_ids, shape=[-1, beam_size, beam_size])
        acc_scores = layers.elementwise_add(
            x=layers.log(topk_scores), y=layers.unsqueeze(pre_scores, axes=[2])
        )
        sel_ids, sel_scores, parents = layers.beam_search(
            pre_ids, pre_scores, topk_ids, acc_scores, beam_size, EOS_IDX
        )

        layers.array_write(sel_ids, i=counter, array=ids_arr)
        layers.array_write(sel_scores, i=counter, array=scores_arr)
        layers.array_write(parents, i=counter, array=parents_arr)

        # reorder token prefixes by parent lane, then append sel_ids at
        # position counter+1
        flat_parents = layers.cast(
            layers.elementwise_add(
                layers.cast(parents, "float32"), row_base
            ),
            "int64",
        )  # [B, beam] flat indices into B*beam
        flat_parents = layers.reshape(flat_parents, shape=[-1])
        tokens_re = layers.gather(tokens, flat_parents)  # [B*beam, L]
        next_onehot = layers.cast(
            layers.equal(col, layers.elementwise_add(layers.elementwise_add(step_f, one), one)),
            "int64",
        )  # 1 at column counter+1
        new_tok = layers.elementwise_mul(
            next_onehot, layers.reshape(sel_ids, shape=[-1, 1]), axis=0
        )
        keep = layers.elementwise_mul(
            tokens_re,
            layers.elementwise_sub(
                layers.fill_constant_batch_size_like(
                    input=tokens_re, shape=[-1, max_out_len], dtype="int64", value=1.0
                ),
                next_onehot,
            ),
        )
        layers.assign(layers.elementwise_add(keep, new_tok), output=tokens)

        layers.assign(layers.reshape(sel_ids, shape=[-1, beam_size]), output=pre_ids)
        layers.assign(sel_scores, output=pre_scores)
        layers.increment(x=counter, value=1, in_place=True)
        layers.less_than(x=counter, y=max_len_const, cond=cond)

    sentence_ids, sentence_scores = layers.beam_search_decode(
        ids_arr, scores_arr, parents_arr, beam_size, EOS_IDX
    )
    return sentence_ids, sentence_scores


def get_inference_model(
    beam_size=4,
    max_out_len=32,
    seq_len=64,
    src_vocab_size=SRC_VOCAB,
    trg_vocab_size=TRG_VOCAB,
    max_length=MAX_LENGTH,
    n_layer=N_LAYER,
    n_head=N_HEAD,
    d_model=D_MODEL,
    d_inner=D_INNER,
):
    """Standalone decode program sharing parameter names with get_model's
    training program (build both under the same fresh unique_name guard)."""
    import paddle_tpu as fluid

    infer = fluid.Program()
    startup = fluid.Program()
    with fluid.program_guard(infer, startup):
        src_word = layers.data(name="src_word", shape=[seq_len], dtype="int64")
        ids, scores = fast_decode(
            src_word, beam_size, max_out_len, src_vocab_size, trg_vocab_size,
            max_length, n_layer, n_head, d_model, d_inner,
        )
    return {"infer": infer, "startup": startup, "ids": ids, "scores": scores,
            "feeds": ["src_word"]}


# ---------------------------------------------------------------------------
# Decode-mode forward: a decoder-only LM over the PAGED KV cache.
#
# fast_decode above trades FLOPs for compile-once (each While step re-runs
# the whole padded prefix).  The serving decode runtime
# (paddle_tpu/serving/decode_scheduler.py) wants the opposite trade: a
# fixed-shape per-TOKEN step that REUSES cached K/V, with the cache paged
# so admission/retirement never reshapes anything.  These functions are
# that forward, written at the jax level (the decode step's whole-loop
# state — paged pools, page tables, slot arrays — has no Program-level
# analog): same Transformer anatomy as the graph above (post-norm blocks,
# scaled embedding + sinusoid positions, bias-free projections), exposed
# through ``build_decode_model`` as the ``DecodeModel`` pair:
#
# * ``lm_prefill``: the padded prompt in one plain causal pass
#   (``mha_reference``, no cache) to the last real token's logits and the
#   per-layer K/V.  Not served: the reference that ``chip_smoke.py``
#   holds the served tokens to.
# * ``lm_prefill_chunk``: one resumable prefill CHUNK over the paged
#   pool — scatter the window's k/v into the sequence's pages, attend
#   through the page table over everything cached so far
#   (``paged_prefill_attention``).  Chunked prefill, prefix-cache
#   resume, AND monolithic prefill (one bucket-wide chunk) all run this
#   step at one fixed attention key width, which is what makes them
#   bitwise interchangeable.
# * ``lm_decode_step``: one token per slot — project q/k/v, scatter k/v
#   into each slot's current page/offset, attend over the slot's own
#   pages (``paged_decode_attention``), finish the block stack, emit
#   logits.  Row-independent end to end, which is what makes continuous
#   batching bitwise-equal to per-sequence serving.
# ---------------------------------------------------------------------------


def lm_params(seed=0, vocab_size=256, n_layer=2, n_head=2, d_model=64,
              d_inner=128, max_length=512):
    """Initialize decoder-only LM weights (numpy f32) + the static meta
    dict ``build_decode_model`` needs.  Returns ``(params, meta)`` —
    ``params`` is a pure array pytree (safe to pass through jit)."""
    rng = np.random.RandomState(seed)

    def w(rows, cols, scale=None):
        s = scale if scale is not None else 1.0 / np.sqrt(rows)
        return (rng.randn(rows, cols) * s).astype(np.float32)

    params = {
        "tok_emb": (rng.randn(vocab_size, d_model) * 0.02).astype(np.float32),
        "pos_table": _position_encoding_table(max_length, d_model),
        "out_w": w(d_model, vocab_size),
        "layers": [
            {
                "wq": w(d_model, d_model), "wk": w(d_model, d_model),
                "wv": w(d_model, d_model), "wo": w(d_model, d_model),
                "ln1_s": np.ones(d_model, np.float32),
                "ln1_b": np.zeros(d_model, np.float32),
                "ffn_w1": w(d_model, d_inner),
                "ffn_b1": np.zeros(d_inner, np.float32),
                "ffn_w2": w(d_inner, d_model),
                "ffn_b2": np.zeros(d_model, np.float32),
                "ln2_s": np.ones(d_model, np.float32),
                "ln2_b": np.zeros(d_model, np.float32),
            }
            for _ in range(n_layer)
        ],
    }
    meta = dict(vocab_size=vocab_size, n_layer=n_layer, n_head=n_head,
                d_model=d_model, d_inner=d_inner, max_length=max_length,
                head_dim=d_model // n_head)
    return params, meta


def _lm_ln(x, scale, bias, eps=1e-5):
    import jax.numpy as jnp

    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * scale + bias


def _lm_block_tail(lp, x, attn_out):
    """Post-norm residual tail shared by prefill and decode: attention
    output projection + LN, then the relu FFN + LN."""
    import jax.numpy as jnp

    x = _lm_ln(x + attn_out @ lp["wo"], lp["ln1_s"], lp["ln1_b"])
    h = jnp.maximum(x @ lp["ffn_w1"] + lp["ffn_b1"], 0.0)
    return _lm_ln(x + h @ lp["ffn_w2"] + lp["ffn_b2"],
                  lp["ln2_s"], lp["ln2_b"])


def lm_prefill(params, tokens, length, *, n_head):
    """Plain causal pass over one padded prompt, the reference beside the
    served chunk program.  ``tokens``: [T] int32 (pad tail arbitrary),
    ``length``: real token count.  Returns ``(last_logits [V],
    k [L, T, H, Dh], v [L, T, H, Dh])``."""
    import jax
    import jax.numpy as jnp

    from ..parallel.flash_attention import mha_reference

    T = tokens.shape[0]
    d_model = params["tok_emb"].shape[1]
    dh = d_model // n_head
    # jnp views: the tables are numpy at rest, but fancy-indexing by a
    # traced token array needs jax arrays
    emb = jnp.asarray(params["tok_emb"])
    x = emb[tokens] * np.sqrt(d_model) + params["pos_table"][:T]
    lens1 = jnp.reshape(jnp.asarray(length, jnp.int32), (1,))
    ks, vs = [], []
    for lp in _lm_layers(params):
        q = (x @ lp["wq"]).reshape(T, n_head, dh)
        k = (x @ lp["wk"]).reshape(T, n_head, dh)
        v = (x @ lp["wv"]).reshape(T, n_head, dh)
        ks.append(k)
        vs.append(v)
        q4 = q.transpose(1, 0, 2)[None]  # [1, H, T, Dh]
        k4 = k.transpose(1, 0, 2)[None]
        v4 = v.transpose(1, 0, 2)[None]
        ctx = mha_reference(q4, k4, v4, causal=True, kv_lens=lens1)
        ctx = ctx[0].transpose(1, 0, 2).reshape(T, d_model)
        x = _lm_block_tail(lp, x, ctx)
    last = jax.lax.dynamic_index_in_dim(x, length - 1, axis=0,
                                        keepdims=False)
    return last @ params["out_w"], jnp.stack(ks), jnp.stack(vs)


# The served pytree holds EIGHT arrays whatever the depth: a dispatch on the
# chip pays about 13 us an argument (45 arrays a dispatch read +5.5 to +7.7%
# on `tfbase_lm_chat`'s `itl_p95_ms`, PERF.md PR 28), while slicing these small
# matrices out of a stack costs the device a copy of 12 MB a layer.  (At
# MiniCPM-SALA's widths the same slice copies 134 MB: there the matrices stay
# one array a layer.)
_LM_ATTN = ("wq", "wk", "wv", "wo")                      # [L, 4, d, d]
_LM_VEC_D = ("ln1_s", "ln1_b", "ffn_b2", "ln2_s", "ln2_b")   # [L, 5, d]


def lm_serving_params(params):
    """``lm_params``' pytree as the serving steps take it: every per-layer
    weight stacked by kind (``attn`` ``[L, 4, d, d]``, ``ffn_w1``, ``ffn_w2``,
    ``vec_d`` ``[L, 5, d]``, ``ffn_b1``) beside the three tables."""
    import jax.numpy as jnp

    layers = params["layers"]

    def stack(names):
        return jnp.stack([jnp.stack([jnp.asarray(lp[n]) for n in names])
                          for lp in layers])

    return {
        "tok_emb": params["tok_emb"], "pos_table": params["pos_table"],
        "out_w": params["out_w"],
        "attn": stack(_LM_ATTN), "vec_d": stack(_LM_VEC_D),
        "ffn_w1": jnp.stack([jnp.asarray(lp["ffn_w1"]) for lp in layers]),
        "ffn_w2": jnp.stack([jnp.asarray(lp["ffn_w2"]) for lp in layers]),
        "ffn_b1": jnp.stack([jnp.asarray(lp["ffn_b1"]) for lp in layers]),
    }


def _lm_layers(params):
    """Per-layer dicts whatever the form: ``lm_params``' own list, or the
    stacks of ``lm_serving_params`` indexed by the (static) layer."""
    if "attn" not in params:
        return params["layers"]
    out = []
    for li in range(params["attn"].shape[0]):
        lp = {n: params["attn"][li, j] for j, n in enumerate(_LM_ATTN)}
        lp.update({n: params["vec_d"][li, j] for j, n in enumerate(_LM_VEC_D)})
        lp.update({n: params[n][li] for n in ("ffn_w1", "ffn_w2", "ffn_b1")})
        out.append(lp)
    return out


def lm_prefill_chunk(params, tokens, start, valid, cache, chunk_pages,
                     gather_pages, slot=None, *, n_head, attn_impl=None):
    """One chunk of a prompt's prefill, resumable at any page boundary.

    ``tokens``: [C] int32 — the chunk's token window (pad tail
    arbitrary), absolute positions ``start .. start + C - 1``;
    ``valid``: real tokens in this window (the final chunk's tail is
    pad); ``cache``: the cache's pytree, of which this model keeps
    ``"k"`` and ``"v"``, the stored stacks
    ``[L, num_pages, page_size, H*Dh]`` (heads folded head-major);
    ``chunk_pages``: [C // page_size] int32 page ids this chunk's
    k/v scatter into (tail entries -> scratch); ``gather_pages``:
    [max_pages] int32 — the sequence's FULL page-table row, what the
    chunk attends over; ``slot`` is unused (no slot state).  Returns
    ``(last_logits [V], cache')`` with ``last_logits`` at row
    ``valid - 1`` (position ``start + valid - 1`` — only the final
    chunk's is meaningful).

    Per layer the chunk's k/v are scattered into the pool FIRST, then
    attention gathers through the page table
    (:func:`~paddle_tpu.parallel.flash_attention.paged_prefill_attention`)
    — so a chunk sees every earlier chunk, any shared prefix-cache
    pages, and itself, causally by absolute position.  The attention
    key width is the fixed full-table span whatever the chunk size, and
    every row is row-independent — which together make monolithic
    (one chunk), chunked, and prefix-cache-resumed prefill bitwise
    interchangeable.
    """
    import jax
    import jax.numpy as jnp

    from ..parallel.flash_attention import paged_prefill_attention

    k_pool, v_pool = cache["k"], cache["v"]
    C = tokens.shape[0]
    ps = k_pool.shape[2]
    nb = C // ps
    d_model = params["tok_emb"].shape[1]
    dh = d_model // n_head
    emb = jnp.asarray(params["tok_emb"])
    pos_table = jnp.asarray(params["pos_table"])
    positions = jnp.minimum(start + jnp.arange(C, dtype=jnp.int32),
                            pos_table.shape[0] - 1)
    x = emb[tokens] * np.sqrt(d_model) + pos_table[positions]
    for li, lp in enumerate(_lm_layers(params)):
        q = (x @ lp["wq"]).reshape(C, n_head, dh)
        # k/v rows are already the pool's folded page rows [H*Dh] (head h =
        # lanes h*dh:(h+1)*dh); scattered into the STACKED pool, which the
        # attention then addresses in place by (li, page): no k_pool[li]
        k_pool = k_pool.at[li, chunk_pages].set(
            (x @ lp["wk"]).reshape(nb, ps, d_model).astype(k_pool.dtype))
        v_pool = v_pool.at[li, chunk_pages].set(
            (x @ lp["wv"]).reshape(nb, ps, d_model).astype(v_pool.dtype))
        ctx = paged_prefill_attention(q, k_pool, v_pool, gather_pages,
                                      start, impl=attn_impl, layer=li)
        x = _lm_block_tail(lp, x, ctx.reshape(C, d_model))
    last = jax.lax.dynamic_index_in_dim(x, valid - 1, axis=0,
                                        keepdims=False)
    return last @ params["out_w"], dict(cache, k=k_pool, v=v_pool)


def lm_decode_step(params, tokens, positions, cache, page_tables, kv_lens,
                   *, n_head, attn_impl=None):
    """One decode iteration: token s of each slot at cache index
    ``positions[s]``.  Writes k/v into the paged pools (``cache["k"]`` /
    ``cache["v"]``, the stored stacks ``[L, num_pages, page_size, H*Dh]``),
    attends over each slot's first ``kv_lens[s]`` cached tokens, returns
    ``(logits [S, V], cache')``.  ``kv_lens[s] == 0`` = inactive slot
    (scratch-page write, zero attention, garbage logits the scheduler
    ignores)."""
    import jax.numpy as jnp

    from ..parallel.flash_attention import paged_decode_attention

    k_pool, v_pool = cache["k"], cache["v"]
    S = tokens.shape[0]
    page_size = k_pool.shape[2]
    d_model = params["tok_emb"].shape[1]
    dh = d_model // n_head
    emb = jnp.asarray(params["tok_emb"])
    pos_table = jnp.asarray(params["pos_table"])
    x = emb[tokens] * np.sqrt(d_model) + pos_table[positions]
    pages = page_tables[jnp.arange(S), positions // page_size]
    offsets = positions % page_size
    for li, lp in enumerate(_lm_layers(params)):
        q = (x @ lp["wq"]).reshape(S, n_head, dh)
        # one folded row [H*Dh] per slot into the STACKED pool, attended in
        # place by (li, page) — see lm_prefill_chunk
        k_pool = k_pool.at[li, pages, offsets].set(
            (x @ lp["wk"]).astype(k_pool.dtype))
        v_pool = v_pool.at[li, pages, offsets].set(
            (x @ lp["wv"]).astype(v_pool.dtype))
        ctx = paged_decode_attention(q, k_pool, v_pool, page_tables,
                                     kv_lens, impl=attn_impl, layer=li)
        x = _lm_block_tail(lp, x, ctx.reshape(S, d_model))
    return x @ params["out_w"], dict(cache, k=k_pool, v=v_pool)


def build_decode_model(params, meta, eos_id=None, attn_impl=None):
    """Wrap LM weights as a serving ``DecodeModel``: the weights ride in
    ``DecodeModel.params`` (:func:`lm_serving_params`) and every step takes
    them as an argument.  ``attn_impl`` ("auto"/"reference"/"pallas") is
    the paged attention engine of the chunk and the decode step both.
    """
    import functools

    from ..serving.decode_scheduler import DecodeModel

    n_head = meta["n_head"]
    return DecodeModel(
        functools.partial(lm_decode_step, n_head=n_head,
                          attn_impl=attn_impl),
        functools.partial(lm_prefill_chunk, n_head=n_head,
                          attn_impl=attn_impl),
        params=lm_serving_params(params),
        num_layers=meta["n_layer"], num_heads=n_head,
        head_dim=meta["head_dim"], vocab_size=meta["vocab_size"],
        eos_id=eos_id, name="transformer-lm")
