"""An EvaByte-family model behind ``serving.InferenceEngine`` ->
``DecodeScheduler`` (``paddle_tpu/models/evabyte.py``): the builders, the
checks against the plain reference at the configuration's own shapes, in the
form the standing loop (``drivers/serve_standing_moe.py``, through
``serve_standing_eva.py``) asks for them.  Every size comes from the
configuration's file (the family's own key names); the rows and bytes a
perfect decode step must move are in ``chipbench/eva_decode.py``."""
from __future__ import annotations

import collections
import functools

import numpy as np

# THE LIMITS OF ``correct``.  Each sits between two readings that every run
# takes side by side (my chip runs, PR 49; the table in PERF.md section 6): the
# sound path's, and the same quantity in the precision BELOW the one the
# configuration states, or under a wrong mechanism.  The second readings are
# ``NOT_JUDGED``: they are in the line so that a limit can be seen to hold.
#   lower precision                         read as                  fails
#   K/V and summary rows in 8 bits          kv_rows_8bit,            kv_rows, summary_rows,
#                                           summary_rows_8bit,       kv_rows_deep (a row),
#                                           kv_rows_deep_8bit_min,   LOGIT_TOL
#                                           logits_8bit_rows
#   summaries pooled in bfloat16            summary_pooling_bf16,    summary_pooling,
#                                           summarise_bf16           summarise
#   probabilities rounded to bfloat16       eva_*_bf16_probabilities eva_decode, eva_prefill
#   wrong mechanism: the two lists' softmaxes apart and averaged
#   (``eva_decode_apart``), a visible count one short
#   (``eva_decode_one_summary_short``)                               eva_decode
#
# Each mechanism stand-alone against the plain reference (float32, highest
# precision) at the configuration's own shapes, max |a - b| / max |b|:
#   eva_decode / eva_prefill: the kernel (one walk over the window's pages and
#     then the summaries', 256 rows a turn, one softmax) and the chunk
#     program's XLA form over bfloat16 pools against the reference's masked
#     attention over the same bfloat16 rows; slots with a window of one row, a
#     full one, no summary and 1920 of them, one empty.  Both products run
#     over exact bfloat16 parts (decode) or at the highest precision
#     (prefill), so nothing is rounded that the reference does not round:
#     1.5e-7 to 2.4e-7 and 0.0 in every run.  The reference itself with its
#     probabilities rounded to bfloat16 before they meet V (what the chip's
#     default precision does to a float32 product) reads 1.9e-4 to 3.4e-4 on
#     the decode rows and 1.8e-3 to 2.8e-3 on the chunk's; the two lists'
#     softmaxes apart and averaged 0.11 to 0.26, one visible summary short
#     0.076 to 0.37.  The limits are a hundred times the sound readings and a
#     tenth and a hundredth of the nearest other.
#   summarise: ``models.evabyte.summarise`` against the reference's summaries
#     of the same bfloat16 rows, float32 pooling at the highest precision on
#     both sides: 0.0; with every operand bfloat16 6.4e-3 to 8.7e-3.
MECHANISM_RTOL = {"eva_decode": 2e-5, "eva_prefill": 2e-5, "summarise": 2e-5}
NOT_JUDGED = ("eva_decode_apart", "eva_decode_one_summary_short",
              "eva_decode_bf16_probabilities",
              "eva_prefill_bf16_probabilities", "summarise_bf16",
              "kv_rows_8bit", "summary_rows_8bit",
              "summary_rows_bf16_pooling", "summary_pooling_bf16",
              "kv_rows_deep_median", "kv_rows_deep_max",
              "kv_rows_deep_8bit_min", "summary_rows_deep_median",
              "summary_rows_deep_max", "logits_8bit_rows", "windows_crossed",
              "summaries_by_decode")
# THE LOGITS OF ALL 8 HEADS (2560 a position) after prefill and after decoding
# through the cache ACROSS a window boundary, against the reference's full
# forward over the same bytes, max |a - b| / std(b): 0.0193 to 0.0238 over 44
# checked requests of 23 runs (mean 0.0209, deviation 0.0011: bfloat16 weights
# and rows through 8 layers).  The same replay with every row of the cache
# rounded to 8 bits after every program (``logits_8bit_rows``, the run's first
# checked request) reads 0.037 to 0.077 over 8 runs: attention averages a
# row's rounding over hundreds of rows, so the logits tell 8 bits from 16 by
# a factor of two to three where a row itself does by twelve.  The limit is
# the geometric middle of the sound readings' largest and the control's
# smallest, eight deviations over the sound mean; ``kv_rows`` and
# ``DEEP_ROW_TOL`` are the limits that fail that control with room.
# Summaries pooled in bfloat16 are NOT read in the logits (the step functions
# have no such form to replay); ``summary_pooling`` is the limit that fails
# them.
# Compared as logits, not bytes: with random weights the largest of 320 flips
# on rounding, so the served bytes are held to the reference in their SHARE
# within ``TIE_TOL`` of its top (1.0 in every run; the largest gap 0.0065).
# The loop's routed-expert comparison has nothing to read here
# (``ROUTING_AGREE`` 0: one column that always agrees).
LOGIT_TOL = 0.03
TIE_TOL = 0.15
CHECKED_TOKENS = 128
TOKENS_AGREE = 0.7
ROUTING_AGREE = 0.0


def make_params(cfg, seed):
    from paddle_tpu import observability as obs
    from paddle_tpu.models import evabyte as M

    with obs.span("serving.model_load", model="evabyte-weights"):
        import jax

        params = M.params(cfg, seed, dtype=cfg["weights_dtype"])
        jax.block_until_ready(params)
    _VOCAB[:] = [cfg["vocab_size"]]
    _CONTROL.clear()
    return params, {"cfg": cfg}


def decode_config(cfg, max_new_tokens):
    from paddle_tpu import serving

    return serving.DecodeConfig(
        num_slots=cfg["slots"], page_size=cfg["page"],
        max_seq_len=cfg["max_seq_len"], num_pages=dict(cfg["num_pages"]),
        prefill_buckets=tuple(cfg["buckets"]),
        prefill_chunk_tokens=cfg["chunk"], prefix_cache=cfg["prefix_cache"],
        max_new_tokens=max_new_tokens, queue_capacity=cfg["queue_capacity"],
        kv_dtype=cfg["kv_dtype"])


def build_engine(cfg, params, meta, max_new_tokens):
    """The front door, warmed up (the constructor compiles the decode step
    and every prefill chunk width)."""
    from paddle_tpu import serving
    from paddle_tpu.models import evabyte as M

    return serving.InferenceEngine(
        decode_model=M.build_decode_model(params, cfg),
        decode_config=decode_config(cfg, max_new_tokens))


def _rel(a, b):
    a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
    if not np.all(np.isfinite(a)):
        return float("inf")
    return float(np.max(np.abs(a - b)) / np.max(np.abs(b)))


def _summarise_bf16(k, v, phi, mu):
    """``models.evabyte.summarise`` with every operand and result bfloat16:
    the lower precision's reading of a summary row."""
    import jax
    import jax.numpy as jnp

    bf = jnp.bfloat16
    k, v, phi, mu = (a.astype(bf) for a in (k, v, phi, mu))
    n = k.shape[0]
    alpha = jax.nn.softmax(jnp.einsum("nchd,hd->nch", k, phi), axis=1)
    return ((jnp.einsum("nch,nchd->nhd", alpha, k) + mu).reshape(n, -1),
            jnp.einsum("nch,nchd->nhd", alpha, v).reshape(n, -1))


def mechanism_errors(cfg, params, seed, reference):
    """The mechanisms as the step programs call them (the engine the program
    picks here) against the plain reference at the configuration's head
    count, widths, page sizes, slots, chunk, window and chunk size, on seeded
    random rows and the served ``phi`` / ``mu`` of layer 0."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.models import evabyte as M
    from paddle_tpu.parallel import flash_attention as FA

    d = M._dims(cfg)
    H, Dh, W, C = d["H"], d["Dh"], d["W"], d["C"]
    ps, S, chunk = cfg["page"], cfg["slots"], cfg["chunk"]
    rpp = cfg["summary_page_rows"]
    n_win = W // ps                                   # pages of one window
    n_sum = max(1, -(-(cfg["max_seq_len"] - W) // (rpp * C)))
    n_sum = min(n_sum, 32)
    ks = jax.random.split(jax.random.PRNGKey((seed + 5) % (2 ** 31)), 8)
    kv_dt = jnp.dtype(cfg["kv_dtype"])
    act = params["layers"][0]["w_qkv"].dtype

    def rows(key, n):
        return jax.random.normal(key, (n, H * Dh), jnp.float32).astype(kv_dt)

    def pool(rows_, perm, page_rows):
        n = perm.shape[0]
        return jnp.zeros((1, n + 1, page_rows, H * Dh), kv_dt).at[
            0, perm].set(rows_.reshape(n, page_rows, -1))

    perm_w = 1 + jax.random.permutation(ks[0], n_win).astype(jnp.int32)
    perm_s = 1 + jax.random.permutation(ks[1], n_sum).astype(jnp.int32)
    k_rows, v_rows = rows(ks[2], W), rows(ks[3], W)
    ks_rows, vs_rows = rows(ks[4], n_sum * rpp), rows(ks[5], n_sum * rpp)
    pools = (pool(k_rows, perm_w, ps), pool(v_rows, perm_w, ps),
             pool(ks_rows, perm_s, rpp), pool(vs_rows, perm_s, rpp))
    f32 = lambda a: a.astype(jnp.float32).reshape(-1, H, Dh)
    errs = {}

    def plain(q, n_w, n_s, p_dtype=jnp.float32):
        """The reference's one softmax: rows see ``n_w [R]`` window rows and
        ``n_s [R]`` summaries (positions inside window 1 of a sequence whose
        window 0 stands for all the summaries).  ``p_dtype`` bfloat16 is the
        reference in the precision below the stated one: the probabilities
        rounded before they meet V, as the chip's default precision does."""
        K = jnp.concatenate([f32(k_rows), f32(ks_rows)])
        V = jnp.concatenate([f32(v_rows), f32(vs_rows)])
        at = jnp.arange(K.shape[0])
        ok = jnp.where(at[None] < W, at[None] < n_w[:, None],
                       at[None] - W < n_s[:, None])
        with jax.default_matmul_precision("highest"):
            s = jnp.einsum("rhd,khd->rhk", q, K) * d["sm_scale"]
            p = jax.nn.softmax(jnp.where(ok[:, None], s, reference.NEG), -1)
            return jnp.einsum("rhk,khd->rhd",
                              p.astype(p_dtype).astype(jnp.float32), V)

    plain_low = functools.partial(plain, p_dtype=jnp.bfloat16)

    # decode: windows of one row, a page, a full window; no summary to all
    lw = np.linspace(1, W, S).astype(np.int32)
    ls = (np.linspace(0, n_sum * rpp, S).astype(np.int32) // 2) * 2
    lw[S // 2] = ls[S // 2] = 0
    lw[0], ls[0], lw[-1], ls[-1] = 1, 0, W, n_sum * rpp
    live = lw > 0
    q = jax.random.normal(ks[6], (S, H, Dh), jnp.float32).astype(act)
    tw = jnp.broadcast_to(perm_w[None], (S, n_win))
    ts = jnp.broadcast_to(perm_s[None], (S, n_sum))
    rows_seen = (q.astype(jnp.float32), jnp.asarray(lw), jnp.asarray(ls))
    want = np.asarray(jax.jit(plain)(*rows_seen))

    def decode(lw_, ls_):
        return np.asarray(jax.jit(
            lambda q, a, b, c, e, tw, ts, lw, ls:
            FA.paged_eva_decode_attention(
                q, a, b, c, e, tw, ts, lw, ls, layer=0,
                sm_scale=d["sm_scale"]))(q, *pools, tw, ts, jnp.asarray(lw_),
                                         jnp.asarray(ls_)))

    got = decode(lw, ls)
    errs["eva_decode"] = _rel(got[live], want[live])
    errs["eva_decode_bf16_probabilities"] = _rel(
        np.asarray(jax.jit(plain_low)(*rows_seen))[live], want[live])
    if got[~live].any():
        errs["eva_decode_empty_slot_not_zero"] = float("inf")
    both = live & (ls > 0)
    errs["eva_decode_apart"] = _rel(
        0.5 * (decode(lw, 0 * ls) + decode(0 * lw, ls))[both], want[both])
    # the visible count one short: the closing window's last chunk unread
    errs["eva_decode_one_summary_short"] = _rel(
        decode(lw, np.maximum(ls - 1, 0))[both], want[both])

    # prefill: one ragged chunk late in a window
    start = W - chunk
    valid = chunk - max(1, chunk // 14)
    qc = jax.random.normal(ks[7], (chunk, H, Dh), jnp.float32).astype(act)
    # the entry point derives the window rows and the visible summaries from
    # ``start``: a start inside the window whose index makes as many of the
    # table's summaries visible as whole windows give
    win_index = n_sum * rpp * C // W
    at = win_index * W + start
    rows_seen = (qc.astype(jnp.float32), start + 1 + jnp.arange(chunk),
                 jnp.full((chunk,), win_index * (W // C)))
    want = np.asarray(jax.jit(plain)(*rows_seen))[:valid]
    got = jax.jit(lambda q, a, b, c, e, pw, ps_: FA.paged_eva_prefill_attention(
        q, a, b, c, e, pw, ps_, jnp.int32(at), W, C, layer=0,
        sm_scale=d["sm_scale"]))(qc, *pools, perm_w, perm_s)
    errs["eva_prefill"] = _rel(np.asarray(got)[:valid], want)
    errs["eva_prefill_bf16_probabilities"] = _rel(
        np.asarray(jax.jit(plain_low)(*rows_seen))[:valid], want)

    # the summary writer on the same rows
    kc, vc = (a.astype(jnp.float32).reshape(-1, C, H, Dh)
              for a in (k_rows, v_rows))
    phi, mu = params["phi"][0], params["mu"][0]
    def pooled(k, v):
        with jax.default_matmul_precision("highest"):
            return reference.summaries(k.reshape(-1, H, Dh),
                                       v.reshape(-1, H, Dh), phi, mu, C)

    want = jax.jit(pooled)(kc, vc)
    got = jax.jit(M.summarise)(kc, vc, phi, mu)
    def err(got):
        return max(_rel(g, np.asarray(w).reshape(g.shape))
                   for g, w in zip(got, want))

    errs["summarise"] = err(got)
    errs["summarise_bf16"] = err(jax.jit(_summarise_bf16)(kc, vc, phi, mu))
    return errs


_REFERENCE_FN = {}
_LAST_SUMMARIES = {}
_CONTROL = {}           # the 8-bit replay's logits, then their error
_VOCAB = [None]         # head 0's width, for :func:`gap` (the loop has no cfg)


def reference_logits(cfg, params, sequence, positions, reference,
                     forced=None):
    """The reference's logits of ALL heads ``[P, 8 * 320]`` at ``positions``
    of ``sequence``, a column of routing that always agrees (the loop's
    routed-expert comparison has nothing to read), and each layer's K and V
    rows there ``[P, 2, H * Dh]``.  Every chunk's summary of every layer is
    kept for :func:`deep_row_errors`.  The sequence is padded to the
    configuration's ``max_seq_len`` and the positions to whole chunks: one
    compiled program for most lengths."""
    import jax
    import jax.numpy as jnp

    block = 64 if cfg["window_size"] % 64 == 0 else cfg["chunk_size"]
    T = -(-cfg["max_seq_len"] // block) * block
    seq = np.zeros(T, np.int32)
    seq[:len(sequence)] = sequence
    n, C = len(positions), cfg["chunk"]
    positions = list(positions) + [positions[-1]] * (-n % C)
    key = (id(reference), len(positions))
    fn = _REFERENCE_FN.get(key)
    if fn is None:
        fn = _REFERENCE_FN[key] = jax.jit(
            lambda p, s, q: reference.forward(p, cfg, s, q, block=block))
    logits, rows, sums = fn(params, jnp.asarray(seq),
                            jnp.asarray(positions, jnp.int32))
    _LAST_SUMMARIES["sums"] = [np.asarray(s, np.float64) for s in sums]
    logits = np.asarray(logits, np.float64).reshape(len(positions), -1)[:n]
    low = _CONTROL.pop("logits_8bit", None)
    if low is not None:
        # the loop asks for the replayed positions first: the control's error
        # in the loop's own measure
        _CONTROL["logits_8bit_rows"] = max(
            float(np.max(np.abs(a - b)) / b.std()) if np.all(np.isfinite(a))
            else float("inf") for a, b in zip(low, logits))
    return (logits,
            [np.zeros((n, 1), bool)],
            [np.asarray(r, np.float64)[:n] for r in rows])


def gap(logits, token):
    """How far ``token`` sits below the top of HEAD 0's ``logits`` (the first
    ``vocab_size`` of the row), in their standard deviations."""
    head0 = logits[:_VOCAB[0]]
    return float((head0.max() - head0[int(token)]) / head0.std())


# ONE SCHEDULE, RUN TWICE over a checked sequence (as ``models/mellum.py``
# does): through the engine's OWN compiled step programs into the engine's OWN
# cache after the drain (:func:`served_state_errors`, which reads the rows of
# both groups they leave), and through the step FUNCTIONS under a ``jax.jit``
# that also returns every head's logits (:func:`replay`), on a cache of the
# cell's size.  ``sequence[:n]`` in chunks of ``chunk``, token ``n`` through
# the narrowest chunk program, then ``N_DECODE`` tokens decoded in slot 0
# while every other slot decodes random bytes on a page of its own.  ``n`` is
# chosen so that the decoded positions CROSS a multiple of the window: ``n`` =
# that multiple less ``HEAD`` (a page), so the closing window's last ``HEAD /
# chunk_size`` chunks are summarised by decode steps, the window is given back
# whole, and six chunks of positions of the next read those summaries beside
# the chunk program's (``_decoded``).  The window group's pages are handed out and given back as
# the scheduler does it.
def _decoded(cfg):
    """``(HEAD, N_DECODE)``: a page of positions before the boundary (64: four
    chunks summarised by decode steps) and six chunks past it."""
    return cfg["page"], cfg["page"] + 6 * cfg["chunk_size"]


def _cut(cfg, sequence, split):
    """``n``: the last multiple of the window that leaves room for the
    decoded tail, less ``HEAD``; without such a multiple (a sequence shorter
    than a window) ``split`` floored to a page."""
    W, ps = cfg["window_size"], cfg["page"]
    head, n_decode = _decoded(cfg)
    room = len(sequence) - 1 - n_decode
    boundary = ((room + head) // W) * W
    n = boundary - head if boundary >= W else (min(split, room) // ps) * ps
    assert n > 0, "a checked sequence is a page and %d bytes" % (n_decode + 1)
    return n


def _schedule(cfg, cache, sequence, split, seed, chunk, decode):
    """Run the schedule above: ``chunk(width, tokens, start, valid, {group:
    pages written}, {group: table row})`` and ``decode(tokens, positions,
    {group: tables}, kv_lens)`` are the two programs.  Returns ``(release,
    first, end, chunk results, decode results, where)``: rows ``first .. end
    - 1`` are the last whole-width chunk's, the narrow chunk's and the
    decoded ones; ``where`` = ``(summary pages, first live window page, its
    pages from there on, windows crossed)``; ``release()`` frees everything."""
    import jax.numpy as jnp

    S, ps, C = cfg["slots"], cfg["page"], cfg["chunk"]
    narrow = min(b for b in list(cfg["buckets"]) + [C] if b <= C)
    n = _cut(cfg, sequence, split)
    end = n + 1 + _decoded(cfg)[1]
    first_g, win = cache.primary_group, "window"
    grp, sps = cache.groups[win], cache.page_size
    width = grp.table_width(cfg["max_seq_len"], C)
    pages = cache.alloc(cache.pages_for(end))
    rest = [cache.alloc(1)[0] for _ in range(S - 1)]
    rest_w = grp.alloc(S - 1)
    tables = np.zeros((S, cache.max_pages_per_seq), np.int32)
    tables[0] = cache.table_row(pages)
    tables[1:, 0] = rest
    ring = np.zeros((S, width), np.int32)
    ring[1:, 0] = rest_w
    held, base, crossed = collections.deque(), [0], [0]
    rng = np.random.RandomState(seed % (2 ** 32))

    def device(a):
        # a copy: the programs run behind the host, which rewrites the table
        return jnp.asarray(a.copy())

    def reach(upto):
        for p in range(base[0] + len(held), -(-upto // ps)):
            held.append(grp.alloc(1)[0])
            ring[0, p % width] = held[-1]

    def leave(next_pos):
        live = grp.first_live_page(next_pos)
        dead = [held.popleft() for _ in range(min(live - base[0], len(held)))]
        for p, page in enumerate(dead, base[0]):
            if ring[0, p % width] == page:
                ring[0, p % width] = 0
        if dead:
            grp.free(dead, released=True)
            base[0] += len(dead)
            crossed[0] += 1

    def one(w, start, valid):
        reach(start + valid)
        tokens = np.zeros(w, np.int32)
        tokens[:valid] = sequence[start:start + valid]
        vec_s = np.asarray([pages[start // sps + i]
                            if start // sps + i < len(pages) else 0
                            for i in range(max(1, w // sps))], np.int32)
        vec_w = np.zeros(w // ps, np.int32)
        for i in range(min(w // ps, -(-(start + valid) // ps) - start // ps)):
            vec_w[i] = ring[0, (start // ps + i) % width]
        out = chunk(w, jnp.asarray(tokens), jnp.int32(start),
                    jnp.int32(valid),
                    {first_g: jnp.asarray(vec_s), win: jnp.asarray(vec_w)},
                    {first_g: jnp.asarray(tables[0]), win: device(ring[0])})
        leave(start + valid)
        return out

    chunks = [one(C, start, min(C, n - start)) for start in range(0, n, C)]
    chunks.append(one(narrow, n, 1))
    steps = []
    for pos in range(n + 1, end):
        reach(pos + 1)
        tokens = rng.randint(0, cfg["vocab_size"], S).astype(np.int32)
        tokens[0] = sequence[pos]
        positions = np.full(S, pos - n, np.int32)
        positions[0] = pos
        steps.append(decode(
            jnp.asarray(tokens), jnp.asarray(positions),
            {first_g: jnp.asarray(tables), win: device(ring)},
            jnp.asarray(positions + 1)))
        leave(pos + 1)

    def release():
        cache.free(pages + rest)
        grp.free(list(held) + rest_w)

    where = (pages, base[0], list(held), crossed[0])
    return release, max(0, ((n - 1) // C) * C), end, chunks, steps, where


def replay_fns(cfg):
    """The two step functions under a ``jax.jit`` of their own that also
    returns every head's logits: made once a run, so that every checked
    request replays through the same executables."""
    import jax

    from paddle_tpu.models import evabyte as M

    donate = () if jax.default_backend() == "cpu" else (1,)
    return (jax.jit(lambda p, c, *a: M.prefill_chunk(
                p, *a[:3], c, *a[3:], cfg=cfg, with_heads=True),
                donate_argnums=donate),
            jax.jit(lambda p, c, *a: M.decode_step(
                p, *a[:2], c, *a[2:], cfg=cfg, with_heads=True),
                donate_argnums=donate))


def fresh_cache(cfg):
    """A cache of the cell's size and groups, as the scheduler builds it."""
    from paddle_tpu import serving
    from paddle_tpu.models import evabyte as M

    layout = M.cache_layout(cfg)
    groups = {g: dict(spec, num_pages=cfg["num_pages"][g])
              for g, spec in layout["page_groups"].items()}
    return serving.PagedKVCache(
        0, None, cfg["page"], 0, 0, cfg["max_seq_len"],
        dtype=cfg["kv_dtype"], num_slots=cfg["slots"],
        page_pools=layout["page_pools"], page_groups=groups)


def _eight_bit():
    """Every leaf of the cache rounded to 8 bits (a sign, 4 of exponent, 3 of
    mantissa: float8 e4m3's grid over the normal range) in place.  By
    ``reduce_precision``, which the compiler keeps: a conversion to float8 and
    back inside one program is a pair it removes on the chip
    (``xla_allow_excess_precision``; my chip run, PR 49: the pair read the
    sound logits to the last digit)."""
    import jax

    donate = () if jax.default_backend() == "cpu" else (0,)
    return jax.jit(lambda pools: {
        name: jax.lax.reduce_precision(leaf, exponent_bits=4, mantissa_bits=3)
        for name, leaf in pools.items()}, donate_argnums=donate)


def replay(cfg, params, sequence, split, seed, fns):
    """The step functions' own LOGITS of all heads on the schedule above
    (``fns`` from :func:`replay_fns`, a fresh cache of the cell's size).
    Returns ``(logits [2 + N_DECODE, 8 * 320] at positions n - 1 .. end - 1,
    sets, first, end)``; ``sets`` is the one column the loop's routing
    comparison reads.  The run's FIRST replay is made once more with every
    row of the cache rounded to 8 bits after every program (the precision
    below the 16 bits the configuration states): its logits' distance from
    the reference's is ``LOGIT_TOL``'s upper reading, ``logits_8bit_rows``
    (:func:`reference_logits` takes it, :func:`deep_row_errors` reports
    it)."""
    import jax.numpy as jnp

    def run(rounded=None):
        cache = fresh_cache(cfg)
        pools = [cache.pools]

        def keep(new):
            pools[0] = rounded(new) if rounded else new

        def chunk(width, tokens, start, valid, written, rows):
            _, new, heads = fns[0](
                params, pools[0], tokens, start, valid, written, rows,
                jnp.int32(0))
            keep(new)
            return np.asarray(heads, np.float64).reshape(-1)

        def decode(tokens, positions, tables, lens):
            _, new, _, heads = fns[1](
                params, pools[0], tokens, positions, tables, lens)
            keep(new)
            return np.asarray(heads[0], np.float64).reshape(-1)

        _, first, end, chunks, steps, _ = _schedule(
            cfg, cache, sequence, split, seed, chunk, decode)
        return np.stack(chunks[-2:] + steps), first, end

    logits, first, end = run()
    if "logits_8bit_rows" not in _CONTROL:
        _CONTROL["logits_8bit"] = run(_eight_bit())[0]
    return logits, [np.zeros((end - first, 1), bool)], first, end


def routing_agreement(served, reference_chosen):
    """The loop's routed-expert comparison: no layer of this family routes."""
    assert served.shape == reference_chosen.shape
    return 1.0, 1.0


# THE ROWS HELD ON THE OBJECT THAT IS TIMED (the engine's own executables on
# the engine's own cache, the schedule above, a window closed on the way).
#   kv_rows: layer 0's K and V rows at every position of the LIVE window at the
#     end (the decode steps' past the boundary).  A first layer's row depends
#     on its byte and position alone, so the reference gives it without the
#     cache, in float32: max |row - reference| / max |reference| over K and V:
#     3.2e-3 to 4.1e-3.  The same rows kept in 8 bits (float8 e4m3:
#     ``kv_rows_8bit``) read 3.4e-2 to 6.1e-2; the limit sits between them in
#     the logarithm.
#   summary_rows: layer 0's summary pair of EVERY chunk of the sequence up to
#     the last boundary (the chunk program's and the decode steps' alike)
#     against the reference's summaries of the reference's rows, same measure:
#     3.1e-3 to 5.3e-3 (the rows' own rounding); in 8 bits
#     (``summary_rows_8bit``) 3.9e-2 to 4.4e-2; the limit is ``kv_rows``'.
#     Pooled in bfloat16 (``summary_rows_bf16_pooling``) they read 5.4e-3 to
#     8.4e-3, UNDER this limit: that control is ``summary_pooling``'s.
#   summary_pooling: the pooling by itself.  The live window's whole chunks
#     (summarised by decode steps, not yet visible) against the SERVED K and V
#     rows of the same chunks pooled in float32 at the highest precision and
#     rounded as the cache rounds: the largest distance of a row from it, in
#     the row's norm: 0.0 in every run (bit-equal).  The same rows pooled with
#     every operand bfloat16 (``summary_pooling_bf16``) read 3.1e-3 to 3.8e-3;
#     the limit, a tenth of that, leaves a row some twenty-five values that
#     round the other way.
#   kv_rows_deep / summary_rows_deep: the same rows of every LATER layer (live
#     window rows; the summaries of the chunks from ``lo`` on), whose inputs
#     passed through attention: the share of (row, layer, K | V) entries whose
#     distance from the reference's row, in the row's own norm, is past
#     ``DEEP_ROW_TOL``; the median and largest distance beside it (3.8e-3 and
#     at most 5.5e-3).  The NEAREST such row kept in 8 bits
#     (``kv_rows_deep_8bit_min``) stands 2.6e-2 off (float8's rounding is
#     2^-4 / sqrt(3) of a binade whatever the row): ``DEEP_ROW_TOL`` is the
#     geometric middle, so that every 8-bit row is past it and no sound one.
#   window_pages_left: pages in use or reserved in the window group when the
#     check begins, after the cancel and the drain (the driver's own wait reads
#     the first group's pages): 0.
SERVED_STATE_TOL = {"kv_rows": 1.4e-2, "summary_rows": 1.4e-2,
                    "summary_pooling": 3e-4,
                    "kv_rows_deep": 1e-2, "summary_rows_deep": 1e-2,
                    "window_pages_left": 0.0}
DEEP_ROW_TOL = 0.012


def served_state_errors(cfg, scheduler, sequence, split, seed, params,
                        reference):
    """``SERVED_STATE_TOL``'s first-layer readings from ``scheduler``'s own
    programs and cache (stopped, every page free), and for
    :func:`deep_row_errors` the rows they left in every layer: ``(errs, (lo,
    [(at, k rows, v rows, c0, k~ rows, v~ rows) per layer]))``: window rows
    from position ``at`` on, summaries from chunk ``c0`` on."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.models import evabyte as M

    d = M._dims(cfg)
    cache, ps, C = scheduler.cache, cfg["page"], d["C"]
    H, Dh = d["H"], d["Dh"]
    zeros = (jnp.zeros((cfg["slots"],), jnp.uint32),
             jnp.zeros((cfg["slots"],), jnp.float32))

    def chunk(width, *args):
        scheduler.run_step(("chunk", width), *args, np.int32(0), np.uint32(0),
                           np.float32(0))

    def decode(*args):
        scheduler.run_step(("decode",), *args, *zeros)

    grp = cache.groups["window"]
    release, errs = None, {"window_pages_left": float(
        grp.used_pages + grp.reserved)}
    try:
        release, first, end, _, _, where = _schedule(
            cfg, cache, sequence, split, seed, chunk, decode)
        pages, live, held, crossed = where
        lo = max(0, first - cfg["chunk"])
        n_sum = (end // d["W"]) * d["W"] // C       # chunks of closed windows
        n_sum = n_sum or end // C                   # a toy: every whole chunk
        rpp = cache.pools["ksum"].shape[2]
        errs["windows_crossed"] = float(crossed)
        # visible chunks whose last byte a decode step wrote
        errs["summaries_by_decode"] = float(max(0, min(n_sum, end // C) - (
            end - _decoded(cfg)[1]) // C))
        at = max(live * ps, lo)

        def window_rows(leaf, layer):
            got = cache.pools[leaf][layer, jnp.asarray(held)].reshape(
                len(held) * ps, -1)
            return got[at - live * ps:end - live * ps]

        def summary_rows(leaf, layer, c0, upto=None):
            upto = n_sum if upto is None else upto
            ids = jnp.asarray(pages[c0 // rpp:-(-upto // rpp)])
            got = cache.pools[leaf][layer, ids].reshape(len(ids) * rpp, -1)
            return got[c0 - (c0 // rpp) * rpp:][:upto - c0]

        c0 = lo // C
        served = [(at,) + tuple(np.asarray(
            window_rows(leaf, layer).astype(jnp.float32), np.float64)
            for leaf in ("k", "v")) + (c0,) + tuple(np.asarray(
                summary_rows(leaf, layer, c0).astype(jnp.float32), np.float64)
                for leaf in ("ksum", "vsum")) for layer in range(d["L"])]

        # layer 0: rows and summaries from the bytes alone, a window at a
        # time (the engine's pools are still on the device beside this)
        span = d["W"]
        tokens = np.zeros(-(-end // span) * span, np.int32)
        tokens[:end] = sequence[:end]

        def layer0(p, t, first):
            with jax.default_matmul_precision("highest"):
                _, k, v = reference.layer_rows(
                    p, cfg, 0, p["embed"].astype(jnp.float32)[t],
                    first + jnp.arange(t.shape[0], dtype=jnp.int32))
                ks, vs = reference.summaries(k, v, p["phi"][0], p["mu"][0], C)
            return (k.reshape(t.shape[0], -1), v.reshape(t.shape[0], -1),
                    ks.reshape(ks.shape[0], -1), vs.reshape(vs.shape[0], -1))

        layer0 = jax.jit(layer0)
        # K and V of the live window's span alone; every chunk's summary
        # (each window's arrays leave the device before the next is made)
        shift = (at // span) * span
        k0, v0, ks0, vs0 = [], [], [], []
        for i in range(0, len(tokens), span):
            k, v, ks, vs = layer0(params, jnp.asarray(tokens[i:i + span]),
                                  jnp.int32(i))
            if i >= shift:
                k0.append(np.asarray(k))
                v0.append(np.asarray(v))
            ks0.append(np.asarray(ks))
            vs0.append(np.asarray(vs))
            del k, v, ks, vs
        k0, v0, ks0, vs0 = (np.concatenate(a) for a in (k0, v0, ks0, vs0))

        def err(got, want):
            return max(_rel(g.astype(jnp.float32), w)
                       for g, w in zip(got, want))

        got = [window_rows(leaf, 0) for leaf in ("k", "v")]
        want = (k0[at - shift:end - shift], v0[at - shift:end - shift])
        errs["kv_rows"] = err(got, want)
        errs["kv_rows_8bit"] = err(
            [g.astype(jnp.float8_e4m3fn) for g in got], want)
        got_sums = [summary_rows(leaf, 0, 0) for leaf in ("ksum", "vsum")]
        errs["summary_rows"] = err(got_sums, (ks0[:n_sum], vs0[:n_sum]))
        errs["summary_rows_8bit"] = err(
            [g.astype(jnp.float8_e4m3fn) for g in got_sums],
            (ks0[:n_sum], vs0[:n_sum]))
        # the pooling alone, the rows' own rounding apart: the live window's
        # whole chunks (decode steps wrote their summaries, not yet visible)
        # against the SERVED rows pooled in float32 and rounded as the cache
        # rounds; the same rows pooled in bfloat16 beside it
        whole = ((end - at) // C) * C
        if whole and at % C == 0:
            kc, vc = (g[:whole].astype(jnp.float32).reshape(-1, C, H, Dh)
                      for g in got)

            def pooled(k, v):
                with jax.default_matmul_precision("highest"):
                    ks, vs = reference.summaries(
                        k.reshape(-1, H, Dh), v.reshape(-1, H, Dh),
                        params["phi"][0], params["mu"][0], C)
                return [a.reshape(a.shape[0], -1).astype(got[0].dtype)
                        for a in (ks, vs)]

            def far(rows, want):
                rows, want = (np.asarray(a.astype(jnp.float32), np.float64)
                              for a in (rows, want))
                return float((np.linalg.norm(rows - want, axis=-1)
                              / np.linalg.norm(want, axis=-1)).max())

            want = jax.jit(pooled)(kc, vc)
            left = [summary_rows(leaf, 0, at // C, (at + whole) // C)
                    for leaf in ("ksum", "vsum")]
            low = jax.jit(_summarise_bf16)(kc, vc, params["phi"][0],
                                           params["mu"][0])
            errs["summary_pooling"] = max(map(far, left, want))
            errs["summary_pooling_bf16"] = max(map(far, low, want))
            # the bfloat16 pooling in ``summary_rows``' own measure, against
            # the reference's summaries of the reference's rows
            these = slice(at // C, (at + whole) // C)
            errs["summary_rows_bf16_pooling"] = err(
                low, (ks0[these], vs0[these]))
    finally:
        if release is not None:
            release()
    return errs, (lo, served)


def deep_row_errors(cfg, first, served, reference_rows):
    """``kv_rows_deep`` and ``summary_rows_deep`` from ``served = (lo, [(at,
    k, v, c0, k~, v~) per layer])``, the reference's K and V rows per layer at
    positions ``lo ..`` (``[n, 2, width]``) and the summaries it kept
    (:func:`reference_logits`)."""
    import ml_dtypes

    lo, layers = served
    sums = _LAST_SUMMARIES.get("sums")
    far_rows, far_sums, far_8bit = [], [], []

    def far(got, want):
        return np.linalg.norm(got - want, axis=-1) / np.maximum(
            np.linalg.norm(want, axis=-1), 1e-30)
    for layer, ((at, k, v, c0, ks, vs), want) in enumerate(
            zip(layers, reference_rows)):
        if not layer:
            continue
        for which, (g, s) in enumerate(((k, ks), (v, vs))):
            if not (np.all(np.isfinite(g)) and np.all(np.isfinite(s))):
                return {"kv_rows_deep": float("inf")}
            w = np.asarray(want, np.float64)[at - lo:at - lo + len(g), which]
            far_rows.append(far(g, w))
            far_8bit.append(far(g.astype(ml_dtypes.float8_e4m3fn).astype(
                np.float64), w))
            w = sums[layer][c0:c0 + len(s), which]
            far_sums.append(far(s, w))
    errs = {}
    if far_8bit:
        errs["kv_rows_deep_8bit_min"] = float(np.concatenate(far_8bit).min())
    if "logits_8bit_rows" in _CONTROL:
        errs["logits_8bit_rows"] = _CONTROL["logits_8bit_rows"]
    for name, far in (("kv_rows_deep", far_rows),
                      ("summary_rows_deep", far_sums)):
        far = np.concatenate(far) if far else np.zeros(1)
        errs.update({name: float((far > DEEP_ROW_TOL).mean()),
                     name + "_median": float(np.median(far)),
                     name + "_max": float(far.max())})
    return errs
