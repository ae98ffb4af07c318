"""GLM-5 (``model_type`` ``glm_moe_dsa``) behind ``serving.InferenceEngine`` ->
``DecodeScheduler``: the family's model file is ``paddle_tpu/models/
deepseek_v3.py`` (the indexer exists because the configuration has
``index_topk``, the share because it has ``experts_held``), so the builders,
the schedule of the replay and the expert-side helpers are those of
``models/deepseek_v3.py`` beside this file; here are what DeepSeek sparse
attention adds to ``correct`` (the indexer's scores, the exact selection, the
attention over a row list and under a mask, the second cache leaf) and every
limit with its two readings.  The bytes a perfect step must move are in
``chipbench/glm5_decode.py``."""
from __future__ import annotations

import os

import numpy as np

from chipbench.registry import Registry

_BASE = Registry(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))).module("models", "deepseek_v3")

make_params = _BASE.make_params
decode_config = _BASE.decode_config
build_engine = _BASE.build_engine
gap = _BASE.gap
routing_agreement = _BASE.routing_agreement
N_DECODE = _BASE.N_DECODE
_rel, _chosen_mask, _schedule = _BASE._rel, _BASE._chosen_mask, _BASE._schedule

# THE LIMITS OF ``correct``, each with what it holds and its two readings (my
# chip runs, PR 52: seven runs at seven seeds on the final selection; the
# table in PERF.md section 6).  Which limit fails a LOWER PRECISION than the
# configuration states: ``SERVED_STATE_TOL``'s ``latent_rows`` (an 8-bit
# latent), ``index_rows`` (an 8-bit ``index_k``) and ``routing_mismatch``
# (bfloat16 router scores).  The others hold the path against a WRONG
# mechanism and say so.
#
# Each mechanism stand-alone against the plain reference (float32, highest
# precision) at the configuration's own shapes, max |a - b| / max |b|:
#   index_scores: ``paged_index_scores`` over a bfloat16 ``index_k`` pool on a
#     shuffled page table against ``reference.index_scores`` over the same
#     rows, on the visible keys of every slot.  Served 1.6e-3 to 2.1e-3
#     (bfloat16 queries against float32); the same pool rounded to 8 bits
#     2.7e-2 to 4.0e-2 (``index_scores_8bit``, judged by nothing: the served
#     leaf is held by ``index_rows``); a wrong scale, a missing ReLU or a page
#     misaddressed reads 0.3 or more.  Three times the largest.
#   selection: ``dsa_keep`` + ``dsa_rows`` on the kernel's OWN scores against
#     ``reference.select`` (a stable argsort) on the same scores: the share of
#     (slot, position) entries that differ.  The selection is exact: 0.0.
#   mla_rows: ``paged_mla_rows_attention`` (the gather through the page table
#     and the absorbed walk over the list) against the reference's EXPANDED
#     attention over the same sets.  Served 1.7e-4 to 2.6e-4 (a softmax over
#     2048 rows averages the rounding); a row misaddressed reads 0.1 or more.
#   mla_prefill_sets: a chunk's absorbed walk under a selection as a mask
#     against the same.  Served 2.7e-3 to 4.2e-3 (a chunk's early rows see
#     few keys and average less).
#   moe_decode / moe_prefill / routing_mismatch: as ``models/deepseek_v3.py``
#     states them, here for a holder of experts [0, 16) of 256 and ``scale``
#     2.5.  Served 1.5e-3 to 1.9e-3; 0.0 (bfloat16 router scores: 2.1e-2 to
#     2.5e-2, ``routing_mismatch_bf16``, judged by nothing).
MECHANISM_RTOL = {"index_scores": 6e-3, "selection": 0.0, "mla_rows": 2e-3,
                  "mla_prefill_sets": 1.5e-2, "moe_decode": 8e-3,
                  "moe_prefill": 8e-3, "routing_mismatch": 2e-3}
ROUTED_ROWS = 1024
NOT_JUDGED = ("routing_mismatch_bf16", "latent_rows_8bit", "index_rows_8bit",
              "latent_rows_deep_unforced", "latent_rows_deep_median",
              "latent_rows_deep_max", "index_scores_8bit")
# TOP-8 AND TOP-2048 ARE DISCRETE CHOICES.  The logits of the step FUNCTIONS
# (:func:`replay`) are compared with the float32 reference computing the
# replayed rows OVER THE SAME EXPERTS AND THE SAME SETS (``forced``,
# ``selected``), max |a - b| in standard deviations of the reference's
# logits: 0.026 to 0.041 read (84 logit rows); the dense family's limit (a
# wrong mechanism reads 0.7 or more; bfloat16 weights and activations are the
# error).
LOGIT_TOL = 0.1
# ... and with the reference on its OWN sets (the experts still forced): a
# served row whose 2048th and 2049th index scores lie closer than bfloat16
# rounding attends to another token of 2048, each such flip a key whose
# weight is among the smallest of the set.  0.038 to 0.059 read; the toy
# control (a window in place of the selection,
# ``tests/chipbench_tests/test_glm5_cell.py``) reads over twice the limit.
OWN_SETS_LOGIT_TOL = 0.15
# a served token counts as the reference's within TIE_TOL of its top logit;
# 0.992 to 1.0 of 128 read (kanana-2's limits, its reasons)
TIE_TOL = 0.15
CHECKED_TOKENS = 128
TOKENS_AGREE = 0.7
# 0.992 to 0.995 of the reference's chosen experts held (sets equal on 0.94
# to 0.96 of the rows): kanana-2's limit
ROUTING_AGREE = 0.95
# THE SELECTION of the replayed rows (the last whole chunk's under the mask,
# the narrow chunk's, the decoded tokens' as row lists: 517 rows a request)
# against the reference's own over the same upstream choices, a layer at a
# time.  A KEY computed along another discrete path upstream (a token whose
# experts in an earlier layer differ: 0.7 to 2% of rows) has another ``k^I``
# from the third layer on, so its score differs by up to 0.29 of the row's
# largest whatever the precision (``score_err_max``, judged by nothing; 6e-3
# to 1.4e-2 in the first two layers, which no expert layer precedes); the
# limits are on what precision and the rule decide:
#   SCORE_RTOL at SCORE_QUANTILE: the 0.9 quantile over a layer's (row,
#     visible key) entries of |served - reference| / the row's largest
#     |reference|.  1.7e-3 (first layer) to 6.2e-3 (fifth) read; 0 to 1.5% of
#     the entries lie past the limit (``score_far_share``: the keys routed
#     apart).  The limit is 2.4 times the largest; an 8-bit ``index_k`` fails
#     ``index_rows`` below.
#   SELECTION_AGREE: the least share, over the rows, of the reference's set
#     that the served set holds.  0.983 (fifth layer) to 0.997 read; 0.4 to
#     2.1% of the set's entries flip; the toy control (the last k) holds
#     under a half.  The issue's 0.97.
#   FLIPS_UNEXPLAINED: the share of flipped positions that lie farther than
#     FLIP_RTOL from BOTH sides' thresholds (the least selected score) on a
#     score that agrees within SCORE_RTOL: a flip is a near tie or a key
#     computed apart, never another rule.  0.0 of 70 (request, layer) pairs;
#     the two thresholds lie 0.8e-3 to 4.9e-3 apart (``tau_shift_max``).
SCORE_RTOL = 1.5e-2
SCORE_QUANTILE = 0.9
SELECTION_AGREE = 0.97
FLIP_RTOL = 1.5e-2
FLIPS_UNEXPLAINED = 0.0
# THE CACHE HELD ON THE OBJECT THAT IS TIMED (the engine's own executables on
# the engine's own cache, the schedule of ``models/deepseek_v3.py``):
#   latent_rows / latent_padding / latent_rows_deep: as that file states them
#     (3.1e-3 to 3.6e-3, in 8 bits 4.2e-2 to 4.7e-2; 0.0; 0.0 of 2068 pairs,
#     the largest sound distance 8.3e-3 under ``DEEP_ROW_TOL`` 0.03), the
#     later layers' rows over the replay's experts AND sets.
#   index_rows: the FIRST layer's rows of ``index_k`` for the whole sequence
#     against ``rope(LayerNorm(norm1(E[tok]) W_k^I))`` in float32, max |row -
#     reference| / max |reference|.  3.2e-3 to 3.8e-3 read; the same rows kept
#     in 8 bits (``index_rows_8bit``, 4 exponent and 3 mantissa bits, read
#     beside it in every run) 4.5e-2 to 5.2e-2.  The limit sits between them
#     in the logarithm: three times the one, a quarter of the other.
SERVED_STATE_TOL = {"latent_rows": 1.4e-2, "latent_padding": 0.0,
                    "latent_rows_deep": 1e-2, "index_rows": 1.2e-2}


def _m():
    from paddle_tpu.models import deepseek_v3 as M

    return M


def mechanism_errors(cfg, params, seed, reference):
    """The mechanisms as the step programs call them against the plain
    reference at the configuration's head counts, widths, page size, slots
    and chunk, on seeded random inputs and the served weights."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.parallel import flash_attention as FA
    from paddle_tpu.parallel import moe

    M = _m()
    d = M._dims(cfg)
    H, dn, dr, R, W = d["H"], d["dn"], d["dr"], d["R"], d["W"]
    Hi, Di, top = d["Hi"], d["Di"], d["topk"]
    ps, C, S = cfg["page"], cfg["chunk"], cfg["slots"]
    T = min(5 * top + 3 * ps + 5, cfg["max_seq_len"] - C)       # ragged
    npg = -(-(T + C) // ps)
    ks = jax.random.split(jax.random.PRNGKey((seed + 5) % (2 ** 31)), 12)
    kv_dt = jnp.dtype(cfg["kv_dtype"])
    act = params["embed"].dtype
    wkvb = params["layers"][0]["wkvb"]
    perm = 1 + jax.random.permutation(ks[1], npg).astype(jnp.int32)
    errs = {}

    # ---- the indexer's scores and the selection, a decode step's shapes
    keys = jax.random.normal(ks[7], (npg * ps, Di), jnp.float32).astype(kv_dt)
    ipool = jnp.zeros((1, npg + 1, ps, Di), kv_dt).at[0, perm].set(
        keys.reshape(npg, ps, Di))
    lens = np.linspace(1, T, S).astype(np.int32)
    lens[S // 2] = 0
    lens[1] = min(top - 7, T)            # fewer visible than the selection
    tables = jnp.broadcast_to(perm[None, :], (S, npg))
    qi = jax.random.normal(ks[8], (S, Hi, Di), jnp.float32)
    wi = jax.random.normal(ks[9], (S, Hi), jnp.float32)
    scale = d["index_scale"]

    def served_scores(q, w, pool, t, n):
        return FA.paged_index_scores(q.astype(act), w, pool, t, n, layer=0,
                                     scale=scale)

    got = np.asarray(jax.jit(served_scores)(qi, wi, ipool, tables,
                                            jnp.asarray(lens)))[:, :T]
    pos = jnp.asarray(np.maximum(lens - 1, 0))
    want = np.asarray(jax.jit(reference.index_scores)(
        qi, wi, keys[:T].astype(jnp.float32), pos))
    seen = np.arange(T)[None, :] < lens[:, None]
    errs["index_scores"] = _rel(np.where(seen, got, 0.0),
                                np.where(seen, want, 0.0))
    if not (got[~seen] <= -1e29).all():
        errs["index_scores_past_kv_lens_not_masked"] = float("inf")
    eight = np.asarray(jax.jit(served_scores)(
        qi, wi, jax.jit(lambda p: jax.lax.reduce_precision(
            p.astype(jnp.float32), 4, 3).astype(p.dtype))(ipool), tables,
        jnp.asarray(lens)))[:, :T]
    errs["index_scores_8bit"] = _rel(np.where(seen, eight, 0.0),
                                     np.where(seen, want, 0.0))

    def pick(s, n):
        keep = FA.dsa_keep(s, n, top)
        rows, m = FA.dsa_rows(keep, top)
        return keep, rows, m

    full = jax.jit(served_scores)(qi, wi, ipool, tables, jnp.asarray(lens))
    keep, rows, n_rows = jax.jit(pick)(full, jnp.asarray(lens))
    keep = np.asarray(keep)[:, :T]
    own = np.array(jax.jit(lambda s, p: reference.select(s, p, top))(
        jnp.where(jnp.asarray(seen), full[:, :T], -jnp.inf), pos))
    own[lens == 0] = False
    listed = np.zeros_like(keep)
    for s_, (r, m) in enumerate(zip(np.asarray(rows), np.asarray(n_rows))):
        listed[s_, r[:m]] = True
    errs["selection"] = float(((keep != own) | (listed != own)).sum()
                              / max(1, own.sum()))

    # ---- latent attention over the row list (decode) and under the mask
    lat = jnp.concatenate([
        jax.random.normal(ks[0], (npg * ps, R + dr), jnp.float32),
        jnp.zeros((npg * ps, W - R - dr), jnp.float32)], axis=1).astype(kv_dt)
    pool = jnp.zeros((1, npg + 1, ps, W), kv_dt).at[0, perm].set(
        lat.reshape(npg, ps, W))
    k_all, v_all = jax.jit(lambda r, w: reference.expand_latent(
        r[:, :R], r[:, R:R + dr], w, dn))(lat[:T + C].astype(jnp.float32),
                                          wkvb)

    def absorbed(q, w):
        q_lat = jnp.einsum("thd,hdc->thc", q[..., :dn].astype(act),
                           w[:, :dn, :], preferred_element_type=jnp.float32)
        return jnp.concatenate([q_lat, q[..., dn:], jnp.zeros(
            q.shape[:2] + (W - R - dr,), jnp.float32)], axis=-1).astype(act)

    def heads(o, w):
        return jnp.einsum("thc,hdc->thd", o.astype(act), w[:, dn:, :],
                          preferred_element_type=jnp.float32)

    q = jax.random.normal(ks[2], (S, H, dn + dr), jnp.float32)
    got = jax.jit(lambda q, pool, w, t, r, n: heads(
        FA.paged_mla_rows_attention(
            absorbed(q, w), pool, t, r, n, v_width=R, sm_scale=d["sm_scale"],
            layer=0), w))(q, pool, wkvb, tables, rows, n_rows)
    sets = np.zeros((S, T + C), bool)
    sets[:, :T] = own
    want = jax.jit(reference.attention)(q, k_all, v_all, jnp.asarray(sets))
    live = lens > 0
    errs["mla_rows"] = _rel(np.asarray(got)[live], np.asarray(want)[live])
    if np.asarray(got)[~live].any():
        errs["mla_rows_empty_slot_not_zero"] = float("inf")

    start = ((T - C) // ps) * ps
    valid = C - max(1, C // 14)
    qc = jax.random.normal(ks[3], (C, H, dn + dr), jnp.float32)
    cpos = start + np.arange(C)
    csets = (np.asarray(jax.random.uniform(ks[10], (C, npg * ps))) < 0.2) & (
        np.arange(npg * ps)[None, :] <= cpos[:, None])
    csets[np.arange(C), cpos] = True        # every row keeps a key it sees
    got = jax.jit(lambda q, pool, w, pages, keep: heads(
        FA.paged_mla_prefill_attention(
            absorbed(q, w), pool, pages, jnp.int32(start), jnp.int32(valid),
            v_width=R, sm_scale=d["sm_scale"], layer=0, keep=keep), w))(
                qc, pool, wkvb, perm, jnp.asarray(csets))
    want = jax.jit(reference.attention)(
        qc, k_all, v_all, jnp.asarray(csets[:, :T + C]))
    errs["mla_prefill_sets"] = _rel(np.asarray(got)[:valid],
                                    np.asarray(want)[:valid])
    del pool, ipool, k_all, v_all

    # ---- the expert layer of a holder of a share
    def layer_weights(p):
        lp = p["layers"][d["n_dense"]]
        return ({"w": p["router_w"][0], "bias": p["router_b"][0]},
                {"w_gu": lp["w_gu"], "w_down": lp["w_down"]})

    def served(p, u):
        router, shared = layer_weights(p)
        return moe.moe_topk(
            u.astype(act), router, {"w_gu": p["e_gu"], "w_down": p["e_down"]},
            shared, top_k=d["k"], experts_held=d["held"], scale=d["scale"],
            layer=0)[0]

    def plain(p, u):
        router, shared = layer_weights(p)
        return reference.moe_layer(
            u, router["w"], router["bias"], p["e_gu"][0], p["e_down"][0],
            (shared["w_gu"], shared["w_down"]), d["k"], d["scale"],
            held=d["held"])

    served, plain = jax.jit(served), jax.jit(plain)
    for name, n, key in (("moe_decode", S, ks[4]), ("moe_prefill", C, ks[5])):
        u = jax.random.normal(key, (n, d["D"]), jnp.float32)
        u = u.astype(act).astype(jnp.float32)
        errs[name] = _rel(served(params, u), plain(params, u)[0])
    u = jax.random.normal(ks[6], (ROUTED_ROWS, d["D"]), jnp.float32)
    w, bias = params["router_w"][0], params["router_b"][0]
    want = np.asarray(jax.jit(lambda u, w, b: reference.route(
        u, w, b, d["k"], d["scale"])[0])(u, w, bias))
    for name, route in (
            ("routing_mismatch", lambda x, w, b: moe.route_topk(
                x, w, b, top_k=d["k"])[0]),
            ("routing_mismatch_bf16", lambda x, w, b: _BASE._route_bf16(
                x, w, b, d["k"]))):
        got = _chosen_mask(jax.jit(route)(u, w, bias), d["router"])
        errs[name] = float((got != want).sum() / want.sum())
    return errs


# what :func:`replay` saw and the reference said of the same rows, a checked
# request an entry: ``moe_check.check`` passes neither on, and
# :func:`selection_checks` reads them once it is done
_REPLAYED = []
_REFERENCE_FN = {}


def replay_fns(cfg):
    """The two step functions under a ``jax.jit`` of their own that also
    returns the routing and the selection."""
    import jax

    M = _m()
    donate = () if jax.default_backend() == "cpu" else (1,)
    return (jax.jit(lambda p, c, *a: M.prefill_chunk(
                p, *a[:3], c, *a[3:], cfg=cfg, with_routing=True,
                with_selection=True), donate_argnums=donate),
            jax.jit(lambda p, c, *a: M.decode_step(
                p, *a[:2], c, *a[2:], cfg=cfg, with_routing=True,
                with_selection=True), donate_argnums=donate))


def replay(cfg, params, sequence, split, seed, fns):
    """The step functions' own LOGITS, ROUTING and SELECTION on the schedule
    of ``models/deepseek_v3.py`` (a fresh cache of the cell's size).  Returns
    ``(logits [2 + N_DECODE, V] at positions n - 1 .. end - 1, sets, first,
    end)``: ``sets`` one ``[end - first, E]`` bool mask per expert layer; the
    selected sets and index scores of rows ``first .. end - 1`` go to
    ``_REPLAYED``."""
    import jax.numpy as jnp

    from paddle_tpu import serving

    M = _m()
    cache = serving.PagedKVCache(
        0, cfg["num_pages"], cfg["page"], 0, 0, cfg["max_seq_len"],
        dtype=cfg["kv_dtype"], num_slots=cfg["slots"], **M.cache_layout(cfg))
    pools = [cache.pools]
    n_exp = M._dims(cfg)["router"]
    T = len(sequence)

    def chunk(width, tokens, start, valid, pages, row):
        logits, pools[0], routing, selection = fns[0](
            params, pools[0], tokens, start, valid, pages, row, jnp.int32(0))
        v = int(valid)
        return (np.asarray(logits, np.float64),
                [_chosen_mask(np.asarray(r)[:v], n_exp) for r in routing],
                [(np.asarray(s[:v, :T]), np.asarray(k[:v, :T]))
                 for s, k in selection])

    def decode(tokens, positions, tables, lens):
        logits, pools[0], _, routing, selection = fns[1](
            params, pools[0], tokens, positions, tables, lens)
        picked = []
        for s, rows, n in selection:
            keep = np.zeros((1, T), bool)
            keep[0, np.asarray(rows[0])[:int(n[0])]] = True
            picked.append((np.asarray(s[:1, :T]), keep))
        return (np.asarray(logits[0], np.float64),
                [_chosen_mask(np.asarray(r)[:1], n_exp) for r in routing],
                picked)

    _, first, end, chunks, steps = _schedule(
        cfg, cache, sequence, split, seed, chunk, decode)
    outs = chunks[-2:] + steps
    sets = [np.concatenate(layer) for layer in zip(*(o[1] for o in outs))]
    _REPLAYED.append({
        "first": first, "end": end, "length": T,
        "logits": np.stack([o[0] for o in outs]),
        "scores": [np.concatenate([x[0] for x in layer])
                   for layer in zip(*(o[2] for o in outs))],
        "sets": [np.concatenate([x[1] for x in layer])
                 for layer in zip(*(o[2] for o in outs))]})
    return np.stack([o[0] for o in outs]), sets, first, end


def _reference(cfg, params, sequence, positions, reference, forced,
               selected):
    import jax
    import jax.numpy as jnp

    block = 128
    T = cfg["max_seq_len"]
    seq = np.zeros(-(-T // block) * block, np.int32)
    seq[:len(sequence)] = sequence
    n, C = len(positions), cfg["chunk"]
    positions = list(positions) + [positions[-1]] * (-n % C)
    pad = C + 1 + N_DECODE - len(forced[0])
    rows = jnp.asarray(list(forced[0]) + [forced[0][-1]] * pad, jnp.int32)
    forced = (rows, [jnp.asarray(np.concatenate([s] + [s[-1:]] * pad))
                     for s in forced[1]])
    if selected is not None:
        def wide(s):
            out = np.zeros((len(s) + pad, len(seq)), bool)
            out[:len(s), :s.shape[1]] = s
            out[len(s):] = out[len(s) - 1]
            return jnp.asarray(out)
        selected = (rows, [wide(s) for s in selected])
    key = (id(reference), len(seq), len(positions), selected is not None)
    fn = _REFERENCE_FN.get(key)
    if fn is None:
        fn = _REFERENCE_FN[key] = jax.jit(
            lambda p, s, q, f, sel: reference.forward(
                p, cfg, s, q, block=block, forced=f, selected=sel))
    logits, chosen, lat, index = fn(params, jnp.asarray(seq), jnp.asarray(
        positions, jnp.int32), forced, selected)
    return (np.asarray(logits[:n], np.float64),
            [np.asarray(c)[:n] for c in chosen],
            [np.asarray(r)[:n] for r in lat], index, n)


def reference_logits(cfg, params, sequence, positions, reference,
                     forced=None):
    """``moe_check.check``'s reference pass: next-token logits ``[P, V]`` at
    ``positions``, each expert layer's own chosen experts there and each
    layer's latent rows there, the forced rows computed over the replay's
    experts AND over the replay's selected sets (the newest entry of
    ``_REPLAYED``, which this call completes with the reference's own index
    scores, sets and ``k^I`` rows at the forced rows)."""
    seen = _REPLAYED[-1]
    logits, chosen, lat, index, n = _reference(
        cfg, params, sequence, positions, reference, forced, seen["sets"])
    own = n - (seen["end"] - seen["first"])        # the forced rows' places
    T = seen["length"]
    seen["reference"] = [
        {"scores": np.asarray(layer["scores"][own:n, :T]),
         "sets": np.asarray(layer["sets"][own:n, :T])} for layer in index]
    seen["call"] = (cfg, sequence, list(positions), forced)
    return logits, chosen, lat


def selection_checks(params, reference):
    """``(bad, readings)`` of the DSA side of every replayed request
    (``_REPLAYED``, which this empties), a layer at a time over the replayed
    rows: the index scores' error, the held share of the reference's set,
    the flips nothing explains, and the replay's logits against the reference
    on its OWN sets."""
    bad, readings = [], []
    for seen in _REPLAYED:
        logits = seen["logits"]
        first, end = seen["first"], seen["end"]
        vis = np.arange(seen["length"])[None, :] <= np.arange(
            first, end)[:, None]
        out = {"context": seen["length"], "score_err": [], "score_err_max": [],
               "score_far_share": [], "set_held_min": [], "flips_share": [],
               "flips_unexplained": [], "tau_shift_max": []}
        for got_s, got_k, ref in zip(seen["scores"], seen["sets"],
                                     seen["reference"]):
            want_s = np.where(vis, ref["scores"], 0.0)
            got_s = np.where(vis, got_s, 0.0)
            top = np.max(np.abs(want_s), axis=1, keepdims=True)
            rel = np.abs(got_s - want_s) / top
            out["score_err"].append(float(np.quantile(rel[vis], SCORE_QUANTILE)))
            out["score_err_max"].append(float(rel.max()))
            out["score_far_share"].append(float((rel[vis] > SCORE_RTOL).mean()))
            both = (got_k & ref["sets"]).sum(axis=1)
            out["set_held_min"].append(float((both / np.maximum(
                ref["sets"].sum(axis=1), 1)).min()))
            # a row's thresholds: the least selected score, on either side
            tau = np.where(ref["sets"], want_s, np.inf).min(axis=1,
                                                            keepdims=True)
            tau_s = np.where(got_k, got_s, np.inf).min(axis=1, keepdims=True)
            out["tau_shift_max"].append(float(np.max(np.abs(tau - tau_s)
                                                     / top)))
            flips = (got_k != ref["sets"]) & vis
            near = np.minimum(np.abs(want_s - tau), np.abs(got_s - tau_s)) / top
            unexplained = flips & (near > FLIP_RTOL) & (rel <= SCORE_RTOL)
            out["flips_share"].append(float(flips.sum() / max(
                1, ref["sets"].sum())))
            out["flips_unexplained"].append(float(
                unexplained.sum() / max(1, flips.sum())))
        cfg, sequence, positions, forced = seen["call"]
        n_logits = len(logits)
        own_logits = _reference(cfg, params, sequence, positions[:n_logits],
                                reference, forced, None)[0]
        out["own_sets_logit_err"] = [
            float(np.max(np.abs(a - b)) / b.std())
            for a, b in zip(logits, own_logits)]
        readings.append(out)
        if not all(e <= SCORE_RTOL for e in out["score_err"]):
            bad.append("index scores vs the reference (the %s quantile of a "
                       "layer's entries): %s" % (SCORE_QUANTILE,
                                                 out["score_err"]))
        if not all(h >= SELECTION_AGREE for h in out["set_held_min"]):
            bad.append("share of the reference's set held, a layer: %s"
                       % out["set_held_min"])
        if not all(f <= FLIPS_UNEXPLAINED for f in out["flips_unexplained"]):
            bad.append("share of the flipped positions that lie far from both "
                       "thresholds on a score that agrees, a layer: %s"
                       % out["flips_unexplained"])
        if not all(e <= OWN_SETS_LOGIT_TOL for e in out["own_sets_logit_err"]):
            bad.append("logits vs the reference on its own sets: %s"
                       % out["own_sets_logit_err"])
    del _REPLAYED[:]
    return bad, readings


def served_state_errors(cfg, scheduler, sequence, split, seed, params,
                        reference):
    """``SERVED_STATE_TOL``'s first-layer readings of BOTH leaves from
    ``scheduler``'s own programs and cache (stopped, every page free), and
    for :func:`deep_row_errors` the latent rows they left in every layer at
    positions ``lo .. end - 1``."""
    import jax
    import jax.numpy as jnp

    M = _m()
    d = M._dims(cfg)
    cache = scheduler.cache
    zeros = (jnp.zeros((cfg["slots"],), jnp.uint32),
             jnp.zeros((cfg["slots"],), jnp.float32))

    def chunk(width, *args):
        scheduler.run_step(("chunk", width), *args, np.int32(0), np.uint32(0),
                           np.float32(0))

    def decode(*args):
        scheduler.run_step(("decode",), *args, *zeros)

    held, errs = [], {}
    try:
        held, first, end, _, _ = _schedule(
            cfg, cache, sequence, split, seed, chunk, decode)
        pages = jnp.asarray(held[:cache.pages_for(end)])
        lo = max(0, first - cfg["chunk"])
        leaf = cache.pools["latent"][0, pages].reshape(-1, d["W"])[:end]
        ileaf = cache.pools["index_k"][0, pages].reshape(-1, d["Di"])[:end]
        got = np.asarray(leaf.astype(jnp.float32), np.float64)
        igot = np.asarray(ileaf.astype(jnp.float32), np.float64)
        deep = np.asarray(cache.pools["latent"][:, pages[lo // cfg["page"]:]]
                          .reshape(d["L"], -1, d["W"])[:, :end - lo]
                          .astype(jnp.float32), np.float64)
        want, iwant = (np.asarray(x, np.float64) for x in jax.jit(
            lambda p, t: first_layer_rows(cfg, p, t, reference))(
                params, jnp.asarray(sequence[:end])))
        width = d["R"] + d["dr"]

        def rel(a, b):
            return (float(np.max(np.abs(a - b)) / np.max(np.abs(b)))
                    if np.all(np.isfinite(a)) else float("inf"))

        def eight(x):
            return np.asarray(jax.lax.reduce_precision(
                x.astype(jnp.float32), 4, 3), np.float64)

        errs["latent_rows"] = rel(got[:, :width], want)
        errs["latent_padding"] = float(max(
            np.max(np.abs(got[:, width:])), np.max(np.abs(deep[..., width:]))))
        errs["latent_rows_8bit"] = rel(eight(leaf)[:, :width], want)
        errs["index_rows"] = rel(igot, iwant)
        errs["index_rows_8bit"] = rel(eight(ileaf), iwant)
    finally:
        cache.free(held)
    return errs, (lo, deep[..., :width])


deep_row_errors = _BASE.deep_row_errors


def first_layer_rows(cfg, params, tokens, reference):
    """``([c | k_pe], k^I)`` the first layer caches for ``tokens`` at
    positions 0.., from the reference's own pieces in float32, each rotary
    part de-interleaved (evens, then odds) as the cache keeps it."""
    import jax
    import jax.numpy as jnp

    with jax.default_matmul_precision("highest"):
        Rq, R, eps = cfg["q_lora_rank"], cfg["kv_lora_rank"], cfg[
            "rms_norm_eps"]
        dr, Di = cfg["qk_rope_head_dim"], cfg["index_head_dim"]
        at = Rq + R + dr
        pos = jnp.arange(tokens.shape[0], dtype=jnp.int32)
        theta = float(cfg["rope_theta"])
        w_in = params["layers"][0]["w_in"].astype(jnp.float32)
        a = reference.rms_norm(params["embed"][tokens].astype(jnp.float32),
                               params["ln1"][0], eps)
        kva = a @ w_in[:, Rq:at]
        k_pe = reference.rope_interleaved(kva[:, R:], pos, theta)
        k_idx = reference.rope_first(reference.layer_norm(
            a @ w_in[:, at:at + Di], params["ikn_w"][0], params["ikn_b"][0]),
            pos, theta, dr)
        return (jnp.concatenate([
            reference.rms_norm(kva[:, :R], params["kvn"][0], eps),
            k_pe[:, 0::2], k_pe[:, 1::2]], axis=1),
            jnp.concatenate([k_idx[:, 0:dr:2], k_idx[:, 1:dr:2],
                             k_idx[:, dr:]], axis=1))
