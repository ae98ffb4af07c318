"""MiniCPM-SALA behind ``serving.InferenceEngine`` -> ``DecodeScheduler``
(``paddle_tpu/models/minicpm_sala.py``): the builders, the checks against the
plain reference at the configuration's own shapes, and the bytes and
operations a perfect decode step must move.  Every size comes from the
configuration's file (the family's own key names)."""
from __future__ import annotations

import numpy as np

# THE LIMITS OF ``correct``, each with what it holds and its readings (my chip
# runs, PR 28; PERF.md section 6 has them by run).  Which limit fails a LOWER
# PRECISION than the configuration states: ``SERVED_STATE_TOL`` below, read
# from the engine's own programs on its own cache, and stand-alone
# ``lightning_update`` and ``selection_mismatch``.  The others hold the path
# against a WRONG mechanism and say so.
#
# Each mechanism stand-alone against the plain reference (float32, highest
# precision) at the configuration's own shapes, max |a - b| / max |b|
# (``selection_mismatch``: differing (row, KV head, block) entries / selected
# entries); lower precision by ``lax.reduce_precision`` (the compiler drops a
# convert pair):
#   lightning_update: the state after one decode step.  Served 0.0 (an
#     elementwise float32 update, the reference's own arithmetic); a bfloat16
#     state 4.7e-3 to 4.9e-3.
#   lightning_step: that step's output q.S.  Served 0.0 (the compiler keeps
#     this contraction off the MXU, in float32); bfloat16 state 1.6e-3 to
#     1.7e-3.
#   selection_mismatch: the served selection against the reference's from the
#     SAME float32 keys.  Served 0.0; scores from bfloat16 operands 1.4e-3 to
#     1.6e-3.
#   sparse_decode / sparse_prefill: the grouped kernels round their matmul
#     operands like the MXU (bfloat16) over bfloat16 pools: 1.8e-3 to 3.0e-3
#     and 1.6e-3 to 4.0e-3 served over 24 seeds.  lightning_chunk /
#     lightning_state: the chunk-wise scan (default-precision einsums) against
#     the recurrence over the same chunk: 2.6e-3 to 3.8e-3 and 2.2e-3 to
#     3.0e-3.  About twice the largest reading (sparse_prefill ranges 2.5
#     times over seeds).  No lower-precision reading: the statistics a lower
#     precision would touch live inside the Pallas kernel and the scan, where
#     nothing outside can round them.  They hold a wrong kernel (a page
#     dropped or misaddressed reads 0.1 or more).
MECHANISM_RTOL = {"sparse_decode": 8e-3, "sparse_prefill": 8e-3,
                  "lightning_chunk": 7e-3, "lightning_state": 7e-3,
                  "lightning_step": 1e-4, "lightning_update": 1e-5,
                  "selection_mismatch": 5e-4}
# next-token LOGITS of the step FUNCTIONS (a second ``jax.jit`` of
# ``sala_prefill_chunk`` / ``sala_decode_step`` that also returns the
# selection, on a cache of the cell's size: NOT the engine's executables,
# which return tokens) against the float32 reference, max |a - b| over the
# vocabulary in standard deviations of the reference's logits: 0.029 to 0.041
# over 60 readings.  It holds the whole path (cache, chunking, selection,
# state carry) against a wrong mechanism; it does NOT tell a lower precision:
# with the state and the pooled keys kept in bfloat16 it reads 0.030 to 0.039
# (the control run through the harness), with bfloat16 scores 0.032 to 0.034:
# bfloat16 weights and activations are the error.  1.7 times the largest.
LOGIT_TOL = 0.07
# a served token may differ from the reference's choice only where the
# reference puts it within TIE_TOL standard deviations (of its logits) of its
# own top logit: two logits each off by up to 0.041 can swap when they are
# 0.082 apart.  Every gap read so far is at most 0.0076 (0.0 in the control
# run: it cannot tell a lower precision either).
TIE_TOL = 0.1
# share of the reference's selected blocks that the served selection holds too
# (the served keys are bfloat16 in the pool, the reference's float32: a near
# tie at the top-k cut swaps the tied blocks; 0.985 to 1.0 read, 0 to 3 blocks
# of 196; 0.990 to 1.0 in the control run).  It holds a wrong RULE (a forced
# block left out is 1 of 34 forced: under 0.97 at once), not a precision.
SELECTION_AGREE = 0.97


def make_params(cfg, seed):
    from paddle_tpu import observability as obs
    from paddle_tpu.models import minicpm_sala as M

    with obs.span("serving.model_load", model="minicpm-sala-weights"):
        import jax

        params = M.sala_params(cfg, seed, dtype=cfg["weights_dtype"])
        jax.block_until_ready(params)
    return params, {"cfg": cfg}


def decode_config(cfg, max_new_tokens):
    from paddle_tpu import serving

    return serving.DecodeConfig(
        num_slots=cfg["slots"], page_size=cfg["page"],
        max_seq_len=cfg["max_seq_len"], num_pages=cfg["num_pages"],
        prefill_buckets=tuple(cfg["buckets"]),
        prefill_chunk_tokens=cfg["chunk"], prefix_cache=cfg["prefix_cache"],
        max_new_tokens=max_new_tokens, queue_capacity=cfg["queue_capacity"],
        kv_dtype=cfg["kv_dtype"])


def build_engine(cfg, params, meta, max_new_tokens):
    """The front door, warmed up (the constructor compiles the decode step
    and every prefill chunk width)."""
    from paddle_tpu import serving
    from paddle_tpu.models import minicpm_sala as M

    return serving.InferenceEngine(
        decode_model=M.build_decode_model(params, cfg),
        decode_config=decode_config(cfg, max_new_tokens))


def _rel(a, b):
    a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
    if not np.all(np.isfinite(a)):
        return float("inf")
    return float(np.max(np.abs(a - b)) / np.max(np.abs(b)))


def mechanism_errors(cfg, seed, reference):
    """The four mechanisms as the step programs call them (the engine the
    program picks here) against the plain reference at the configuration's
    head counts, widths, page size and chunk, on seeded random inputs."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.models import minicpm_sala as M
    from paddle_tpu.parallel import flash_attention as FA

    d = M._dims(cfg)
    sp = cfg["sparse_config"]
    Hq, Hkv, Dh, B = d["Hq"], d["Hkv"], d["Dh"], d["B"]
    ps, C = cfg["page"], cfg["chunk"]
    T = 4 * sp["dense_len"] // 2 + 3 * B + 5         # past dense_len, ragged
    T = min(T, cfg["max_seq_len"] - C)
    npg = -(-(T + C) // ps)
    ks = jax.random.split(jax.random.PRNGKey((seed + 5) % (2 ** 31)), 8)
    kv_dt = jnp.dtype(cfg["kv_dtype"])
    k = jax.random.normal(ks[0], (npg * ps, Hkv, Dh), jnp.float32).astype(kv_dt)
    v = jax.random.normal(ks[1], (npg * ps, Hkv, Dh), jnp.float32).astype(kv_dt)
    perm = 1 + jax.random.permutation(ks[2], npg).astype(jnp.int32)
    pool_k = jnp.zeros((1, npg + 1, ps, Hkv * Dh), kv_dt).at[0, perm].set(
        k.reshape(npg, ps, Hkv * Dh))
    pool_v = jnp.zeros((1, npg + 1, ps, Hkv * Dh), kv_dt).at[0, perm].set(
        v.reshape(npg, ps, Hkv * Dh))
    kf, vf = k.astype(jnp.float32), v.astype(jnp.float32)
    errs = {}

    # sparse decode: a few slots at different lengths, the real selection
    lens = np.asarray([T, sp["dense_len"] + B + 1, sp["dense_len"] // 2, 0],
                      np.int32)
    S = len(lens)
    q = jax.random.normal(ks[3], (S, Hq, Dh), jnp.float32)
    pos = jnp.asarray(np.maximum(lens - 1, 0))
    blocks = jax.jit(lambda q, k, p: reference.select(q, k, p, sp))(
        q, kf[:T], pos)                                       # [S,Hkv,NBt]
    nb_t = blocks.shape[-1]
    mask = jnp.zeros((S, Hkv, npg), bool).at[:, :, :nb_t].set(blocks) & (
        jnp.asarray(lens)[:, None, None] > 0)
    tables = jnp.broadcast_to(perm[None, :], (S, npg))
    sel = M._listed(d, mask, jnp.asarray(lens), tables)
    got = jax.jit(lambda *a: FA.paged_decode_attention(
        a[0], a[1], a[2], tables, jnp.asarray(lens), layer=0,
        selection=(a[3], a[4])))(q, pool_k, pool_v, *sel)
    want = jax.jit(lambda q, k, v, p, b: reference.sparse_attention(
        q, k, v, p, b, B))(q, kf[:T], vf[:T], pos, blocks)
    live = lens > 0
    errs["sparse_decode"] = _rel(np.asarray(got)[live], np.asarray(want)[live])
    if np.asarray(got)[~live].any():
        errs["sparse_decode_empty_slot_not_zero"] = float("inf")

    # sparse prefill: one chunk of queries past dense_len, the real selection
    start = ((T - C) // ps) * ps
    qc = jax.random.normal(ks[4], (C, Hq, Dh), jnp.float32)
    posc = start + jnp.arange(C, dtype=jnp.int32)
    end = start + C
    blocks = jax.jit(lambda q, k, p: reference.select(q, k, p, sp))(
        qc, kf[:end], posc)
    mask = jnp.zeros((C, Hkv, npg), bool).at[:, :, :blocks.shape[-1]].set(
        blocks)
    got = jax.jit(lambda q, pk, pv, m: FA.paged_prefill_attention(
        q, pk, pv, perm, jnp.int32(start), layer=0,
        block_mask=m.transpose(1, 0, 2)))(qc, pool_k, pool_v, mask)
    want = jax.jit(lambda q, k, v, p, b: reference.sparse_attention(
        q, k, v, p, b, B))(qc, kf[:end], vf[:end], posc, blocks)
    errs["sparse_prefill"] = _rel(got, want)

    # lightning: the chunk-wise scan over one chunk, then one decode step
    Hl, Dl = d["Hl"], d["Dl"]
    ql, kl, vl = (jax.random.normal(kk, (C, Hl, Dl), jnp.float32)
                  for kk in ks[5:8])
    s0 = jax.random.normal(ks[0], (Hl, Dl, Dl), jnp.float32)
    valid = C - max(1, C // 14)           # a ragged last chunk
    got_o, got_s = jax.jit(lambda q, k, v, s: M._lightning_chunk(
        d, M.lightning_slopes(Hl), q, k, v, s, jnp.int32(valid)))(
            ql, kl, vl, s0)
    want_o, want_s = jax.jit(lambda q, k, v, s: reference.lightning_recurrence(
        q, k, v, s, valid))(ql, kl, vl, s0)
    errs["lightning_chunk"] = _rel(np.asarray(got_o)[:valid],
                                   np.asarray(want_o)[:valid])
    errs["lightning_state"] = _rel(got_s, want_s)
    # one decode step for a few slots: the UPDATE is elementwise float32, so
    # it is held to float32 rounding, not to the matmuls' bfloat16 operands
    S = 4
    sS = jnp.stack([s0 * (i + 1) for i in range(S)])
    got_o, got_s = jax.jit(lambda q, k, v, s: M._lightning_step(
        jnp.asarray(M.lightning_slopes(Hl)), q, k, v, s,
        jnp.ones((S,), bool)))(ql[:S], kl[:S], vl[:S], sS)
    want = [jax.jit(lambda q, k, v, s: reference.lightning_recurrence(
        q, k, v, s))(ql[i:i + 1], kl[i:i + 1], vl[i:i + 1], sS[i])
        for i in range(S)]
    errs["lightning_step"] = _rel(got_o, np.stack([np.asarray(o)[0]
                                                   for o, _ in want]))
    errs["lightning_update"] = _rel(got_s, np.stack([np.asarray(s)
                                                     for _, s in want]))

    # the selection itself, from the SAME keys on both sides (half-kernel
    # means against kernel means): the share of (row, KV head, block) entries
    # on which the served selection and the reference's differ
    R = 64
    hb = kf[:(T // sp["kernel_stride"]) * sp["kernel_stride"]].reshape(
        -1, sp["kernel_stride"], Hkv, Dh).mean(axis=1)
    rows = jnp.sort(jax.random.randint(ks[2], (R,), sp["dense_len"], T))
    qs = jax.random.normal(ks[6], (R, Hq, Dh), jnp.float32)
    want = np.asarray(jax.jit(lambda q, k, p: reference.select(q, k, p, sp))(
        qs, kf[:T], rows))
    got = np.asarray(jax.jit(lambda q, hb, n: M.select_blocks(
        d, q[None], hb[None], n[None]))(qs, hb, rows + 1))[0]
    nb = min(got.shape[-1], want.shape[-1])
    errs["selection_mismatch"] = float(
        (got[..., :nb] != want[..., :nb]).sum() / want.sum())
    return errs


def reference_logits(cfg, params, sequence, positions, reference):
    """The reference's next-token logits ``[P, V]`` at ``positions`` of
    ``sequence`` (padded to the configuration's ``max_seq_len``: one compiled
    program whatever the length), and each sparse layer's selected blocks."""
    import jax
    import jax.numpy as jnp

    block = 128 if cfg["max_seq_len"] % 128 == 0 else cfg["page"]
    seq = np.zeros(-(-cfg["max_seq_len"] // block) * block, np.int32)
    seq[:len(sequence)] = sequence
    fn = _REFERENCE_FN.get(id(reference))
    if fn is None:
        fn = _REFERENCE_FN[id(reference)] = jax.jit(
            lambda p, s, q: reference.forward(p, cfg, s, q, block=block))
    logits, selected = fn(params, jnp.asarray(seq),
                          jnp.asarray(positions, jnp.int32))
    return np.asarray(logits, np.float64), [np.asarray(s) for s in selected]


_REFERENCE_FN = {}


def gap(logits, token):
    """How far ``token`` sits below the top of ``logits``, in their standard
    deviations (0 where it is the top)."""
    return float((logits.max() - logits[int(token)]) / logits.std())


def replay(cfg, params, sequence, split):
    """The step programs' own LOGITS at the timed shapes: ``sequence[:split]``
    is prefilled chunk by chunk into slot 0 of a fresh cache of the cell's
    size through the jitted ``prefill_chunk_fn`` (logits of its last row:
    position ``split - 1``), then ``sequence[split]`` is fed to the jitted
    ``decode_fn`` over all ``slots`` (logits at position ``split``).  Returns
    ``(chunk_logits, decode_logits, chunk_selection, decode_selection)``,
    the selections one ``[Hkv, NB]`` mask per sparse layer."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu import serving
    from paddle_tpu.models import minicpm_sala as M

    layout = M.cache_layout(cfg)
    cache = serving.PagedKVCache(
        layout["num_layers"], cfg["num_pages"], cfg["page"],
        layout["num_heads"], layout["head_dim"], cfg["max_seq_len"],
        dtype=cfg["kv_dtype"], page_pools=layout["page_pools"],
        slot_state=layout["slot_state"], num_slots=cfg["slots"])
    S, ps, C = cfg["slots"], cfg["page"], cfg["chunk"]
    pages = cache.alloc(cache.pages_for(split + 1))
    row = cache.table_row(pages)
    pools = cache.pools
    donate = () if jax.default_backend() == "cpu" else (1,)
    chunk = jax.jit(lambda p, c, *a: M.sala_prefill_chunk(
        p, *a[:3], c, *a[3:], cfg=cfg, with_selection=True),
        donate_argnums=donate)
    decode = jax.jit(lambda p, c, *a: M.sala_decode_step(
        p, *a[:2], c, *a[2:], cfg=cfg, with_selection=True),
        donate_argnums=donate)
    start = 0
    while start < split:
        valid = min(C, split - start)
        tokens = np.zeros(C, np.int32)
        tokens[:valid] = sequence[start:start + valid]
        vec = np.zeros(C // ps, np.int32)
        n = min(C // ps, len(pages) - start // ps)
        vec[:n] = pages[start // ps:start // ps + n]
        logits, pools, masks = chunk(
            params, pools, jnp.asarray(tokens), jnp.int32(start),
            jnp.int32(valid), jnp.asarray(vec), jnp.asarray(row), jnp.int32(0))
        start += valid
    chunk_sel = [np.asarray(m)[valid - 1] for m in masks]
    tables = np.zeros((S, cache.max_pages_per_seq), np.int32)
    tables[0] = row
    toks, pos, lens = (np.zeros(S, np.int32) for _ in range(3))
    toks[0], pos[0], lens[0] = sequence[split], split, split + 1
    dlogits, pools, _, masks = decode(
        params, pools, jnp.asarray(toks), jnp.asarray(pos),
        jnp.asarray(tables), jnp.asarray(lens))
    out = (np.asarray(logits, np.float64), np.asarray(dlogits[0], np.float64),
           chunk_sel, [np.asarray(m)[0] for m in masks])
    del pools, cache
    return out


# The cache's guarantees HELD ON THE OBJECT THAT IS TIMED: after the window
# and the drain the engine's OWN compiled step programs (the executables of
# the window: ``DecodeScheduler.run_step``, every slot, the cell's pools) run
# a served sequence once more into the engine's OWN cache, and the leaves they
# leave are read.  Nothing here needs the reference: each is an identity of the
# stated float32 arithmetic that a lower precision breaks.
#   lightning_carry_decode / lightning_carry_chunk: over ONE token the state
#     moves by ``S' - lambda S = k^T v``, a rank-one matrix per head.  The
#     share of ``S' - lambda S`` (float64, from the leaf before and after one
#     decode step of every slot / one one-token chunk) outside its best
#     rank-one approximation: float32 rounding of a state of size |S| leaves
#     about 1e-7 |S| / |k^T v|; a state kept or carried in bfloat16 leaves
#     4e-3 |S| / |k^T v| (|S| is 1 to 30 times |k^T v| by the head's decay).
#   pooled_keys: a row of ``kbar`` is the float32 mean of its half-kernel's
#     keys as the ``k`` leaf holds them (``paddle_tpu/models/minicpm_sala.py:
#     _as_stored``), rows written by chunks and rows summed token by token in
#     decode alike: max |kbar - mean(k)| / max |kbar|.  Float32 sums of 16
#     values read about 1e-7; a bfloat16 row is off by up to 2e-3 to 4e-3.
# Readings (PERF.md section 6 has them by run), served -> both leaves rounded
# to bfloat16 after every program (the control: through the harness on the
# chip it comes out not correct by these readings alone; ``tests/
# chipbench_tests/test_minicpm_sala_cell.py`` keeps it on the CPU):
#   lightning_carry_decode  8.7e-7..9.9e-7 -> 2.3e-2   (CPU toy 1.4e-6..3.1e-6 -> 4.9e-2)
#   lightning_carry_chunk   1.3e-5..1.4e-5 -> 2.1e-2   (CPU toy 1.4e-6..3.0e-6 -> 3.3e-2)
#   pooled_keys             2.5e-8..4.7e-8 -> 5.6e-3   (CPU toy 4.6e-8 -> 2.8e-3)
# Each limit sits about as far from both readings in the logarithm.
SERVED_STATE_TOL = {"lightning_carry_decode": 5e-4,
                    "lightning_carry_chunk": 5e-4, "pooled_keys": 1e-5}


def _off_rank_one(before, after, decay):
    """``before, after [..., H, d, d]`` float32, ``decay [H]``: the largest,
    over the leading axes and heads, share of ``after - decay * before``
    (float64, Frobenius norm) outside its best rank-one approximation."""
    delta = after.astype(np.float64) - decay.astype(np.float64)[
        :, None, None] * before.astype(np.float64)
    sv = np.linalg.svd(delta, compute_uv=False)
    if not np.all(np.isfinite(sv)):
        return float("inf")
    total = np.sqrt((sv ** 2).sum(-1))
    rest = np.sqrt((sv[..., 1:] ** 2).sum(-1))
    return float(np.max(rest / np.maximum(total, np.finfo(np.float64).tiny)))


def served_state_errors(cfg, scheduler, sequence, seed):
    """``SERVED_STATE_TOL``'s readings from ``scheduler``'s own programs and
    cache (stopped, every page free): ``sequence`` (a served prompt and its
    answer) is prefilled chunk by chunk into slot 0 up to a page boundary
    ``n``, a ONE-token chunk of the narrowest warmed width carries the state
    over ``sequence[n]``, then two half-kernels less one token are decoded
    with EVERY slot live (the other slots on a page each, their lightning
    state as the window left it).  Read: the ``lin`` leaf of slot 0 and three
    others by the seed around the one-token chunk and around the first decode step, and slot
    0's ``k`` and ``kbar`` rows."""
    import jax.numpy as jnp

    from paddle_tpu.models import minicpm_sala as M

    d = M._dims(cfg)
    cache = scheduler.cache
    S, ps, C, s = cfg["slots"], cfg["page"], cfg["chunk"], d["s"]
    narrow = min(b for b in list(cfg["buckets"]) + [C] if b <= C)
    n = ((len(sequence) - 2 * s) // ps) * ps
    end = n + 2 * s
    pages = cache.alloc(cache.pages_for(end))
    rest = [cache.alloc(1)[0] for _ in range(S - 1)]
    tables = np.zeros((S, cache.max_pages_per_seq), np.int32)
    tables[0] = cache.table_row(pages)
    tables[1:, 0] = rest
    rng = np.random.RandomState(seed % (2 ** 32))
    slots = np.asarray([0] + sorted(
        1 + rng.permutation(S - 1)[:3]))
    decay = np.exp(-M.lightning_slopes(d["Hl"])).astype(np.float32)

    def state():
        return np.asarray(cache.pools["lin"][:, jnp.asarray(slots)])

    def chunk(width, start, valid):
        tokens = np.zeros(width, np.int32)
        tokens[:valid] = sequence[start:start + valid]
        vec = np.zeros(width // ps, np.int32)
        m = max(0, min(width // ps, len(pages) - start // ps))
        vec[:m] = pages[start // ps:start // ps + m]
        return scheduler.run_step(
            ("chunk", width), jnp.asarray(tokens), jnp.int32(start),
            jnp.int32(valid), jnp.asarray(vec), jnp.asarray(tables[0]),
            np.int32(0), np.uint32(0), np.float32(0))

    errs = {}
    try:
        for start in range(0, n, C):
            chunk(C, start, min(C, n - start))
        before = state()
        chunk(narrow, n, 1)
        mid = state()
        errs["lightning_carry_chunk"] = _off_rank_one(
            before[:, :1], mid[:, :1], decay)
        for pos in range(n + 1, end):
            tokens = rng.randint(0, cfg["vocab_size"], S).astype(np.int32)
            tokens[0] = sequence[pos]
            positions = np.full(S, pos - n, np.int32)
            positions[0] = pos
            scheduler.run_step(
                ("decode",), jnp.asarray(tokens), jnp.asarray(positions),
                jnp.asarray(tables), jnp.asarray(positions + 1),
                jnp.zeros((S,), jnp.uint32), jnp.zeros((S,), jnp.float32))
            if pos == n + 1:
                errs["lightning_carry_decode"] = _off_rank_one(
                    mid, state(), decay)
        idx = jnp.asarray(pages)
        k = np.asarray(cache.pools["k"][:, idx].astype(jnp.float32),
                       np.float64).reshape(d["n_sparse"], -1, s,
                                           d["Hkv"] * d["Dh"])[:, :end // s]
        kbar = np.asarray(cache.pools["kbar"][:, idx], np.float64).reshape(
            d["n_sparse"], -1, d["Hkv"] * d["Dh"])[:, :end // s]
        errs["pooled_keys"] = (
            float(np.max(np.abs(kbar - k.mean(axis=2))) / np.max(np.abs(kbar)))
            if np.all(np.isfinite(kbar)) and np.max(np.abs(kbar)) > 0
            else float("inf"))
    finally:
        cache.free(pages + rest)
    return errs


def selection_agreement(served, reference_blocks):
    """Share of the reference's selected blocks (``[Hkv, NBr]``) that the
    served mask (``[Hkv, NB]``) holds, and how many the served one adds."""
    nb = reference_blocks.shape[-1]
    served = served[..., :nb]
    both = int((served & reference_blocks).sum())
    return both / max(1, int(reference_blocks.sum())), int(
        (served & ~reference_blocks).sum())


# -- what a perfect decode step must move -------------------------------------

def weight_bytes(cfg):
    """Bytes of weights a decode step reads: every layer's four matrices and
    the head; of the embedding only the rows looked up."""
    from paddle_tpu.models import minicpm_sala as M

    d = M._dims(cfg)
    item = 2 if cfg["weights_dtype"] == "bfloat16" else 4
    per = {"minicpm4": 2 * d["Hq"] * d["Dh"] + 2 * d["Hkv"] * d["Dh"]
           + d["Hq"] * d["Dh"],
           "lightning-attn": 5 * d["Hl"] * d["Dl"]}
    n = sum(d["D"] * (per[k] + 3 * d["F"]) for k in d["kinds"])
    return item * (n + d["D"] * d["V"] + cfg["slots"] * d["D"])


def sparse_bytes(cfg, selected_tokens, visible_tokens):
    """Bytes the sparse layers must read in one step: K and V rows of the
    selected tokens, and the pooled keys of every visible token (one float32
    row of one KV head's width per ``kernel_stride`` tokens).  The token
    counts are the step's own, summed over slots, sparse layers and KV heads
    (``serving.decode.sparse.*``)."""
    sp = cfg["sparse_config"]
    kv = 2 if cfg["kv_dtype"] == "bfloat16" else 4
    return (selected_tokens * cfg["head_dim"] * 2 * kv
            + visible_tokens / sp["kernel_stride"] * cfg["head_dim"] * 4)


def state_bytes(cfg, active_slots):
    """Bytes of lightning state a step reads and writes."""
    from paddle_tpu.models import minicpm_sala as M

    d = M._dims(cfg)
    return 2 * 4 * active_slots * d["n_lin"] * d["Hl"] * d["Dl"] * d["Dl"]


def sparse_flops(cfg, selected_tokens):
    """Operations of the selected-page attention in one step (q.k and p.v for
    the 16 query heads of each KV head's group)."""
    g = cfg["num_attention_heads"] // cfg["num_key_value_heads"]
    return 4 * g * cfg["head_dim"] * selected_tokens
