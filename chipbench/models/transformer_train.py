"""Encoder-decoder Transformer training through the program's normal path
(``models/transformer.py`` graph, ``optimizer.minimize``, bf16 the way a user
asks for it).  Builders copied from ``chip_smoke.py`` (PR 21), which ran on the
chip; the smoke stays a pass/fail gate and the yardstick does not move with it.
"""
from __future__ import annotations

import math

import numpy as np

from chipbench import flops

FEEDS = ("src_word", "trg_word", "lbl_word")
# bf16 has 8 significand bits; the flash kernel rounds p before p.v and ds in
# its backward, so kernel and reference differ by a few bf16 steps of the
# largest value (PR 21 measured 0.0025 .. 0.0081 at [64,8,256,64])
KERNEL_RTOL = 2e-2


def build(cfg, mix):
    """(main, startup, loss): flash kernels on, bf16 through
    ``mixed_precision.decorate``, built with ``optimizer.minimize``."""
    import paddle_tpu as fluid
    from paddle_tpu import layers, optimizer
    from paddle_tpu.contrib import mixed_precision
    from paddle_tpu.models import transformer as T

    opt = cfg["optimizer"]
    main, startup = fluid.Program(), fluid.Program()
    with fluid.unique_name.guard(), fluid.program_guard(main, startup):
        words = [layers.data(name=n, shape=[mix["seq"]], dtype="int64")
                 for n in FEEDS]
        loss, _, _, _ = T.transformer(
            *words, cfg["vocab"], cfg["vocab"], mix["seq"], cfg["n_layer"],
            cfg["n_head"], cfg["d_model"], cfg["d_inner"], cfg["dropout"],
            label_smooth_eps=cfg["label_smooth_eps"],
            use_flash=cfg["use_flash"])
        mixed_precision.decorate(optimizer.AdamOptimizer(
            learning_rate=opt["lr"], beta1=opt["beta1"], beta2=opt["beta2"],
            epsilon=opt["epsilon"])).minimize(loss)
    return main, startup, loss


def batches(cfg, mix, seed):
    """``distinct_batches`` feed dicts of words from ``seed``, Zipf-distributed
    (p ~ 1 / (rank + 10)) as words are: with uniform words there is nothing to
    learn, and whether the loss of a short run falls is then a coin's toss
    (PR 23 saw it rise with one seed of two); with a skewed unigram
    distribution the first tens of steps have to lower it."""
    rng = np.random.RandomState(seed % (2 ** 32))
    words = np.arange(1, cfg["vocab"])
    p = 1.0 / (words + 10.0)
    p /= p.sum()
    return [{n: rng.choice(words, size=(mix["batch"], mix["seq"]), p=p
                           ).astype(np.int64) for n in FEEDS}
            for _ in range(mix["distinct_batches"])]


def items_per_step(cfg, mix):
    assert cfg["item"] == "token"
    return mix["batch"] * mix["seq"]      # one target position each


def flops_per_step(cfg, mix):
    return flops.transformer_train_step(cfg, mix["batch"], mix["seq"])["total"]


def check(cfg, mix, seed, losses, reference):
    """What must hold of a run's losses, and the flash kernel against the
    configuration's plain reference at the cell's own shape and the dtype
    ``decorate`` hands it (f32).  Returns the list of failures."""
    bad = []
    losses = np.asarray(losses, np.float64)
    if not len(losses) or not np.all(np.isfinite(losses)):
        return ["non-finite loss"]
    want = math.log(cfg["vocab"])
    # a uniform prediction gives cross entropy ln(vocab); random weights at
    # the published widths start within a fifth of a percent of it (PR 21)
    if abs(losses[0] - want) > cfg["first_loss_rtol"] * want:
        bad.append("first loss %.4f not within %g of ln(vocab) %.4f"
                   % (losses[0], cfg["first_loss_rtol"], want))
    k = min(8, len(losses) // 2)
    if k and not losses[-k:].mean() < losses[:k].mean():
        bad.append("loss did not fall: first %d mean %.4f, last %.4f"
                   % (k, losses[:k].mean(), losses[-k:].mean()))
    if cfg["use_flash"]:
        errs = kernel_errors(cfg, mix, seed, reference)
        if not max(errs.values()) <= KERNEL_RTOL:
            bad.append("flash kernel vs reference: %s" % errs)
    return bad


def kernel_errors(cfg, mix, seed, reference):
    """flash_attention (causal, kv_lens) against the plain reference: output
    and the three gradients, max |a - b| / max |b| each."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.parallel.flash_attention import flash_attention

    shape = (mix["batch"], cfg["n_head"], mix["seq"],
             cfg["d_model"] // cfg["n_head"])
    ks = jax.random.split(jax.random.PRNGKey(seed % (2 ** 31)), 5)
    q, k, v, w = (jax.random.normal(kk, shape, jnp.float32) for kk in ks[:4])
    lens = jax.random.randint(ks[4], (shape[0],), shape[2] // 4,
                              shape[2] + 1).astype(jnp.int32)

    def run(attn):
        # every array is an argument: one closed over would be a constant of
        # the executable (tens of MB in the compile cache)
        def f(q, k, v, w, lens):
            out = attn(q, k, v, causal=True, kv_lens=lens)
            return jnp.sum(out * w), out
        (_, out), grads = jax.jit(jax.value_and_grad(
            f, argnums=(0, 1, 2), has_aux=True))(q, k, v, w, lens)
        return (out,) + grads

    errs = {}
    for name, a, b in zip(("out", "dq", "dk", "dv"), run(flash_attention),
                          run(reference.attention)):
        a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
        errs[name] = (float(np.max(np.abs(a - b)) / np.max(np.abs(b)))
                      if np.all(np.isfinite(a)) else float("inf"))
    return errs
