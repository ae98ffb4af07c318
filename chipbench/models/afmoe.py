"""One holder's share of an AFMoE-family model behind ``serving.InferenceEngine``
-> ``DecodeScheduler`` (``paddle_tpu/models/afmoe.py``): the builders and the
checks against the plain reference at the configuration's own shapes.  Every
size comes from the configuration's file (the family's own key names).  The
cache is the Mellum family's (two page groups, the window group's table a
ring), so the schedule that hands window pages out and takes them back, the
reference's padded call, the measures of distance and the deep rows' judgment
are ``models/mellum.py``'s, read from that file; what a perfect step must move
is in ``chipbench/trinity_serve.py``."""
from __future__ import annotations

import os

import numpy as np

from chipbench.registry import Registry

_MELLUM = Registry(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))).module("models", "mellum")
N_DECODE = _MELLUM.N_DECODE
_rel, _chosen_mask = _MELLUM._rel, _MELLUM._chosen_mask
gap, routing_agreement = _MELLUM.gap, _MELLUM.routing_agreement
reference_logits = _MELLUM.reference_logits
deep_row_errors = _MELLUM.deep_row_errors

# THE LIMITS OF ``correct``, each with what it holds and its two readings (my
# chip runs, PR 42; the table in PERF.md section 6).  Which limit fails a
# LOWER PRECISION than the configuration states: ``SERVED_STATE_TOL``'s
# ``kv_rows`` (an 8-bit K/V row), ``routing_mismatch`` (bfloat16 router
# scores), ``TOKENS_AGREE`` and ``kv_rows_deep`` (8-bit weight matrices in the
# engine).  Which fails a WRONG MECHANISM: ``window_decode`` /
# ``window_prefill`` (a window of 4095 or 4097), ``kv_rows`` (no QK-norm, no
# rotary on the sliding layer 5), ``kv_rows_deep`` (rotary in the full layer,
# none in a sliding one).  The readings of each variant are taken in every run
# beside the sound one (``NOT_JUDGED``).  A bias that WEIGHS is held by
# ``tests/unittests/test_afmoe.py`` in float32 logits, not here: a bias of 0.02
# moves a weight by 2%, which read 1.4e-2 to 2.6e-2 in ``moe_*``'s measure in
# two chip runs of PR 42 (three to five times the limit) and is not read again.
#
# Each mechanism stand-alone against the plain reference (float32, highest
# precision) at the configuration's own shapes, max |a - b| / max |b|:
#   full_decode / window_decode / full_prefill / window_prefill: the walk over
#     bfloat16 pools (48 query heads, 6 a KV head; the window's first page
#     masked, its table a ring of 73 columns) against the reference's masked
#     attention over the same bfloat16 rows, slots at ``kv_len`` under, at and
#     over the window, and one ragged chunk late in the sequence.
#   moe_decode / moe_prefill: ``moe_topk(scoring="sigmoid", experts_held=(0,
#     32))`` under the router of 256 with the shared expert, at a decode
#     step's and a chunk's rows, against the reference's loop over the 32 held
#     experts, the served weights of expert layer 0.  ``*_pair_dropped``: what
#     the measure reads where ONE held (row, expert) pair is dropped, the one
#     of least weight (its term over the block's largest entry): 0.30 to 0.46
#     at these shapes against 1.4e-3 to 2.2e-3 sound.
#   routing_mismatch: the share of (row, expert) entries on which the served
#     router's chosen sets differ from the reference's, from the SAME float32
#     rows; ``routing_mismatch_bf16`` from bfloat16 logits.
MECHANISM_RTOL = {"full_decode": 1e-3, "window_decode": 1e-3,
                  "full_prefill": 2e-3, "window_prefill": 2e-3,
                  "moe_decode": 5e-3, "moe_prefill": 5e-3,
                  "routing_mismatch": 2e-3}
ROUTED_ROWS = 1024      # rows the router alone is read on
NOT_JUDGED = ("routing_mismatch_bf16", "kv_rows_8bit",
              "moe_decode_pair_dropped", "moe_prefill_pair_dropped",
              "window_decode_short", "window_decode_long",
              "window_prefill_short", "window_prefill_long",
              "kv_rows_deep_unforced", "kv_rows_deep_median",
              "kv_rows_deep_max", "k_rows_no_qk_norm", "k_rows_no_rotary",
              "k_rows_rotary_in_full", "window_pages_reused",
              "window_pages_taken_before")
# TOP-4 IS A DISCRETE CHOICE (PR 33's finding for top-6 holds): the logits are
# compared OVER THE SAME EXPERTS (the reference's ``forced``), the choice
# itself apart, and the served tokens are held to the reference in their
# SHARE: of up to ``CHECKED_TOKENS`` tokens of each of the mix's
# ``checked_sequences`` served requests (512 tokens a run where the answers
# are long enough), ``TOKENS_AGREE`` lie within ``TIE_TOL`` standard
# deviations of its top logit.  Sound, a sequence reads 0.969 to 1.0 (38
# sequences of 64 tokens, 56 of 73 to 128: my chip runs, PR 42); served from weight
# MATRICES ROUNDED TO 8 BITS (float8 e4m3, the precision below the bfloat16
# the configuration states; the reference, the replay and the mechanisms on
# the sound weights) 0.741 to 0.844 (8 sequences, 2 runs, which also fail
# ``kv_rows_deep``: every deep row past its tolerance); another request's
# tokens read 0.0.  The limit lies between, nearer the control: a sound
# sequence of 128 at 0.985 is 7 standard deviations above it, the control's
# highest reading 1.6 of its own below.
LOGIT_TOL = 0.1
TIE_TOL = 0.15
CHECKED_TOKENS = 128
TOKENS_AGREE = 0.9
ROUTING_AGREE = 0.95


def _model():
    from paddle_tpu.models import afmoe as A

    return A


def make_params(cfg, seed):
    from paddle_tpu import observability as obs

    with obs.span("serving.model_load", model="afmoe-weights"):
        import jax

        params = _model().params(cfg, seed, dtype=cfg["weights_dtype"])
        jax.block_until_ready(params)
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

    return serving.InferenceEngine(
        decode_model=_model().build_decode_model(params, cfg),
        decode_config=decode_config(cfg, max_new_tokens))


def mechanism_errors(cfg, params, seed, reference):
    """The mechanisms as the step programs call them (the engine the program
    picks here) against the plain reference at the configuration's head
    counts, widths, page size, slots, chunk and window, on seeded random
    inputs and the served weights of the first expert layer."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.parallel import flash_attention as FA
    from paddle_tpu.parallel import moe

    d = _model()._dims(cfg)
    H, Hkv, Dh, W = d["H"], d["Hkv"], d["Dh"], d["W"]
    ps, C, S = cfg["page"], cfg["chunk"], cfg["slots"]
    T = min(W + 3 * C + 3 * ps + 5, cfg["max_seq_len"] - C)       # ragged
    npg = -(-(T + C) // ps)
    ks = jax.random.split(jax.random.PRNGKey((seed + 5) % (2 ** 31)), 10)
    kv_dt = jnp.dtype(cfg["kv_dtype"])
    act = params["embed"].dtype
    k_rows = jax.random.normal(ks[0], (npg * ps, Hkv * Dh), jnp.float32
                               ).astype(kv_dt)
    v_rows = jax.random.normal(ks[7], (npg * ps, Hkv * Dh), jnp.float32
                               ).astype(kv_dt)
    perm = 1 + jax.random.permutation(ks[1], npg).astype(jnp.int32)

    def pool(rows):
        return jnp.zeros((1, npg + 1, ps, Hkv * Dh), kv_dt).at[0, perm].set(
            rows.reshape(npg, ps, -1))

    k_pool, v_pool = pool(k_rows), pool(v_rows)
    k_all = k_rows.astype(jnp.float32).reshape(-1, Hkv, Dh)
    v_all = v_rows.astype(jnp.float32).reshape(-1, Hkv, Dh)
    errs = {}
    plain = jax.jit(reference.attention, static_argnums=(4,))
    kinds = (("full", None, None), ("window", W, W),
             ("window", W - 1, W, "_short"), ("window", W + 1, W, "_long"))

    # decode: slots from one key to the whole pool, some at the window's
    # edge, one empty
    lens = np.linspace(1, T, S).astype(np.int32)
    lens[S // 2] = 0
    for i, n in enumerate((W - 1, W, W + 1, W + ps, T)):
        if i + 1 < S and 0 < n <= T:
            lens[i + 1] = n
    live = lens > 0
    q = jax.random.normal(ks[2], (S, H, Dh), jnp.float32).astype(act)
    tables = jnp.broadcast_to(perm[None, :], (S, npg))
    want = {w: np.asarray(plain(
        q.astype(jnp.float32), k_all, v_all,
        jnp.asarray(np.maximum(lens - 1, 0)), w)) for w in (None, W)}
    for kind, window, ref_w, *tag in kinds:
        got = np.asarray(jax.jit(
            lambda q, k, v, t, n, window=window: FA.paged_gqa_decode_attention(
                q, k, v, t, n, layer=0, window=window,
                sm_scale=d["sm_scale"]))(q, k_pool, v_pool, tables,
                                         jnp.asarray(lens)))
        name = kind + "_decode" + "".join(tag)
        errs[name] = _rel(got[live], want[ref_w][live])
        if got[~live].any():
            errs[name + "_empty_slot_not_zero"] = float("inf")

    # prefill: one ragged chunk late in the sequence
    start = ((T - C) // ps) * ps
    valid = C - max(1, C // 14)
    qc = jax.random.normal(ks[3], (C, H, Dh), jnp.float32).astype(act)
    rows = start + jnp.arange(C, dtype=jnp.int32)
    want = {w: np.asarray(plain(qc.astype(jnp.float32), k_all, v_all, rows,
                                w))[:valid] for w in (None, W)}
    for kind, window, ref_w, *tag in kinds:
        got = jax.jit(
            lambda q, k, v, pages, window=window:
            FA.paged_gqa_prefill_attention(
                q, k, v, pages, jnp.int32(start), jnp.int32(valid), layer=0,
                window=window, sm_scale=d["sm_scale"]))(qc, k_pool, v_pool,
                                                        perm)
        errs[kind + "_prefill" + "".join(tag)] = _rel(
            np.asarray(got)[:valid], want[ref_w])
    del k_pool, v_pool, k_all, v_all

    # the expert layer at a decode step's and at a chunk's rows
    first = d["n_dense"]                   # the first expert layer

    def served(p, u):
        lp = p["layers"][first]
        return moe.moe_topk(
            u.astype(act), {"w": p["router_w"][0], "bias": p["router_b"][0]},
            {"w_gu": p["e_gu"], "w_down": p["e_down"]},
            {"w_gu": lp["s_gu"], "w_down": lp["s_down"]}, top_k=d["k"],
            experts_held=d["held"], scale=d["scale"], scoring="sigmoid",
            layer=0)[0]

    def loop(p, u):
        lp = p["layers"][first]
        return reference.moe_layer(
            u, p["router_w"][0], p["router_b"][0], p["e_gu"][0],
            p["e_down"][0], (lp["s_gu"], lp["s_down"]), d["k"], d["scale"],
            held=d["held"])[0]

    def lightest_pair(p, u):
        """The term of the held (row, expert) pair of least weight: what the
        block's output loses where the program DROPS that one pair."""
        lo, hi = d["held"]
        _, w = reference.route(u, p["router_w"][0], p["router_b"][0], d["k"],
                               d["scale"])
        w = w[:, lo:hi]
        at = jnp.argmin(jnp.where(w > 0, w, jnp.inf))
        r, e = at // (hi - lo), at % (hi - lo)
        with jax.default_matmul_precision("highest"):
            term = w[r, e] * reference.swiglu(
                u[r][None], p["e_gu"][0, e], p["e_down"][0, e])
        return jnp.abs(term).max()

    served, loop = jax.jit(served), jax.jit(loop)
    for name, n, key in (("moe_decode", S, ks[4]), ("moe_prefill", C, ks[5])):
        u = jax.random.normal(key, (n, d["D"]), jnp.float32)
        u = u.astype(act).astype(jnp.float32)       # the same rows both sides
        want = np.asarray(loop(params, u))
        errs[name] = _rel(served(params, u), want)
        errs[name + "_pair_dropped"] = float(
            jax.jit(lightest_pair)(params, u) / np.abs(want).max())
    # the router alone, from the same float32 rows on both sides
    u = jax.random.normal(ks[6], (ROUTED_ROWS, d["D"]), jnp.float32)
    w, b = params["router_w"][0], params["router_b"][0]
    want = np.asarray(jax.jit(lambda u, w, b: reference.route(
        u, w, b, d["k"], d["scale"])[0])(u, w, b))
    for name, route in (
            ("routing_mismatch", lambda x, w, b: moe.route_topk(
                x, w, b, top_k=d["k"], scoring="sigmoid")[0]),
            ("routing_mismatch_bf16", lambda x, w, b: _route_bf16(
                x, w, b, d["k"]))):
        got = _chosen_mask(jax.jit(route)(u, w, b), d["E"])
        errs[name] = float((got != want).sum() / want.sum())
    errs.update(k_row_variants(cfg, params, seed, reference))
    return errs


def _route_bf16(x, w, b, top_k):
    """The experts a router would choose whose logits come from bfloat16
    operands and are kept in bfloat16: the lower precision's reading."""
    import jax
    import jax.numpy as jnp

    logits = jnp.dot(x.astype(jnp.bfloat16), w.astype(jnp.bfloat16),
                     preferred_element_type=jnp.float32)
    return jax.lax.top_k(jax.nn.sigmoid(
        jax.lax.reduce_precision(logits, 8, 7)) + b, top_k)[1]


def k_row_variants(cfg, params, seed, reference, rows=256):
    """What a WRONG reading of the block would leave in a K row, in the
    measure of ``kv_rows_deep`` (a row's distance from the reference's, in the
    row's own norm), the least over ``rows`` random rows at positions spread
    over the served range: no QK-norm, no rotary in a sliding layer, rotary in
    the full layer.  Each must lie past ``DEEP_ROW_TOL``; judged by nothing,
    read in every run."""
    rng = np.random.RandomState((seed + 11) % (2 ** 32))
    d = cfg["head_dim"]
    pos = rng.randint(1, cfg["max_seq_len"], size=rows)
    k = rng.standard_normal((rows, 1, d)).astype(np.float32)
    kn = np.asarray(params["kn"][0], np.float32)
    normed = np.asarray(reference.rms_norm(k, kn, cfg["rms_norm_eps"]))
    rotated = np.asarray(reference.rope(normed, pos, cfg["rope_theta"]))

    def least(got, want):
        return float((np.linalg.norm(got - want, axis=-1)
                      / np.linalg.norm(want, axis=-1)).min())

    return {"k_rows_no_qk_norm": least(
                np.asarray(reference.rope(k, pos, cfg["rope_theta"])), rotated),
            "k_rows_no_rotary": least(normed, rotated),
            "k_rows_rotary_in_full": least(rotated, normed)}


# ONE SCHEDULE, RUN TWICE over a checked sequence (``models/mellum.py``'s
# ``_schedule``: chunks of ``chunk``, the narrowest chunk program, ``N_DECODE``
# decoded tokens in slot 0 beside random ones, the window group's pages handed
# out and given back as the scheduler does it with all but a few of its free
# pages held aside): through the engine's OWN compiled step programs into the
# engine's OWN cache after the drain (:func:`served_state_errors`), and through
# the step FUNCTIONS under a ``jax.jit`` that also returns their logits and the
# experts ``moe_topk`` chose (:func:`replay`), on a cache of the cell's size.

def replay_fns(cfg):
    """The two step functions under a ``jax.jit`` of their own that also
    returns the routing: made once a run."""
    import jax

    A = _model()
    donate = () if jax.default_backend() == "cpu" else (1,)
    return (jax.jit(lambda p, c, *a: A.prefill_chunk(
                p, *a[:3], c, *a[3:], cfg=cfg, with_routing=True),
                donate_argnums=donate),
            jax.jit(lambda p, c, *a: A.decode_step(
                p, *a[:2], c, *a[2:], cfg=cfg, with_routing=True),
                donate_argnums=donate))


def fresh_cache(cfg):
    """A cache of the cell's size and groups, as the scheduler builds it."""
    from paddle_tpu import serving

    layout = _model().cache_layout(cfg)
    groups = {g: dict(spec, num_pages=cfg["num_pages"][g])
              for g, spec in layout["page_groups"].items()}
    return serving.PagedKVCache(
        0, None, cfg["page"], 0, 0, cfg["max_seq_len"],
        dtype=cfg["kv_dtype"], num_slots=cfg["slots"],
        page_pools=layout["page_pools"], page_groups=groups)


def replay(cfg, params, sequence, split, seed, fns):
    """The step functions' own LOGITS and ROUTING on the schedule (``fns``
    from :func:`replay_fns`, a fresh cache of the cell's size).  Returns
    ``(logits [2 + N_DECODE, V] at positions n - 1 .. end - 1, sets, first,
    end)``: ``sets`` one ``[end - first, E]`` bool mask per EXPERT layer, the
    experts ``moe_topk`` computed rows ``first .. end - 1`` over."""
    import jax.numpy as jnp

    cache = fresh_cache(cfg)
    pools = [cache.pools]
    n_exp = cfg["router_experts"]

    def chunk(width, tokens, start, valid, written, rows):
        logits, pools[0], _, routing = fns[0](
            params, pools[0], tokens, start, valid, written, rows,
            jnp.int32(0))
        return (np.asarray(logits, np.float64),
                [_chosen_mask(np.asarray(r)[:int(valid)], n_exp)
                 for r in routing])

    def decode(tokens, positions, tables, lens):
        logits, pools[0], _, routing = fns[1](
            params, pools[0], tokens, positions, tables, lens)
        return (np.asarray(logits[0], np.float64),
                [_chosen_mask(np.asarray(r)[:1], n_exp) for r in routing])

    _, first, end, chunks, steps, _ = _MELLUM._schedule(
        cfg, cache, sequence, split, seed, chunk, decode)
    outs = chunks[-2:] + steps
    sets = [np.concatenate(layer) for layer in zip(*(o[1] for o in outs))]
    return np.stack([o[0] for o in outs]), sets, first, end


# THE K AND V ROWS HELD ON THE OBJECT THAT IS TIMED (the engine's own
# executables on the engine's own cache after the window and the drain: every
# window page the check takes was taken and given back by the window's own
# requests before, ``window_pages_taken_before`` counts the group's hand-outs
# up to then; pages the checked sequence itself releases and takes again on
# the way are ``window_pages_reused``).
#   kv_rows: layer 0's K and V rows (published layer 5, a sliding layer: the
#     window group's leaves) at every position still live at the end.  A first
#     layer's row depends on its token and position alone (``rotary(norm_k(
#     norm_in(E[tok] sqrt(D)) W_k))``, ``.. W_v``), so the reference gives it
#     without the cache, in float32: max |row - reference| / max |reference|
#     over K and V.  The same rows kept in 8 bits (float8 e4m3:
#     ``kv_rows_8bit``) is the lower precision's reading.
#   kv_rows_deep: the rows of every LATER layer (6-9; the full group's layer 7
#     among them) at positions ``first .. end - 1``, which the reference
#     computes over the experts the step functions' replay reports
#     (``forced``): the share of (row, layer, K | V) entries whose distance
#     from the reference's row, in the row's own norm, is past
#     ``DEEP_ROW_TOL``.  It holds the ENGINE'S executables to the routing the
#     replay reports and to the rotation of each kind of layer.
#   window_pages_left / full_pages_left: pages in use or reserved in each group
#     when the check begins, after the drain: 0.
SERVED_STATE_TOL = {"kv_rows": 1.4e-2, "kv_rows_deep": 1e-2,
                    "window_pages_left": 0.0, "full_pages_left": 0.0}
DEEP_ROW_TOL = _MELLUM.DEEP_ROW_TOL


def served_state_errors(cfg, scheduler, sequence, split, seed, params,
                        reference):
    """``SERVED_STATE_TOL``'s first-layer readings from ``scheduler``'s own
    programs and cache (stopped, every page free), and for
    :func:`deep_row_errors` the rows they left in every layer at positions
    ``lo ..`` (a sliding layer's no earlier than its first live page):
    ``(errs, (lo, [(at, k rows, v rows) per layer]))``."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.models.mellum import GROUPS

    d = _model()._dims(cfg)
    cache, ps = scheduler.cache, cfg["page"]
    zeros = (jnp.zeros((cfg["slots"],), jnp.uint32),
             jnp.zeros((cfg["slots"],), jnp.float32))

    def chunk(width, *args):
        scheduler.run_step(("chunk", width), *args, np.int32(0), np.uint32(0),
                           np.float32(0))

    def decode(*args):
        scheduler.run_step(("decode",), *args, *zeros)

    grp = cache.groups["window"]
    release, errs = None, {
        "window_pages_left": float(grp.used_pages + grp.reserved),
        "full_pages_left": float(cache.stats()["used_pages"]),
        "window_pages_taken_before": float(grp.taken)}
    try:
        release, first, end, _, _, where = _MELLUM._schedule(
            cfg, cache, sequence, split, seed, chunk, decode)
        pages, live, held, again = where
        lo = max(0, first - cfg["chunk"])
        errs["window_pages_reused"] = float(again)

        def rows(leaf, row, ids, at):
            """Rows ``at .. end - 1`` of layer ``row`` of ``leaf`` as float32,
            ``ids`` the pages from position ``(at // ps) * ps`` on."""
            got = cache.pools[leaf][row, jnp.asarray(ids)].reshape(
                len(ids) * ps, -1)
            skip = at - (at // ps) * ps
            return got[skip:skip + end - at]

        served = []
        for layer, kind in enumerate(d["kinds"]):
            _, kn, vn = GROUPS[kind]
            if kind == "full_attention":
                at, ids = lo, pages[lo // ps:cache.pages_for(end)]
            else:
                at = max(lo, live * ps)
                ids = held[at // ps - live:]
            served.append((at,) + tuple(np.asarray(
                rows(leaf, d["row"][layer], ids, at).astype(jnp.float32),
                np.float64) for leaf in (kn, vn)))
        # layer 0, every live position
        kind = d["kinds"][0]
        _, kn, vn = GROUPS[kind]
        at = 0 if kind == "full_attention" else live * ps
        ids = pages[:cache.pages_for(end)] if kind == "full_attention" else held
        got = [rows(leaf, 0, ids, at) for leaf in (kn, vn)]
        want = jax.jit(lambda p, t: reference.layer_rows(
            p, cfg, 0, p["embed"][t].astype(jnp.float32) * d["emb"],
            at + jnp.arange(t.shape[0], dtype=jnp.int32)))(
                params, jnp.asarray(sequence[at:end]))

        def err(got):
            return max(_rel(g.astype(jnp.float32), w)
                       for g, w in zip(got, want))

        errs["kv_rows"] = err(got)
        errs["kv_rows_8bit"] = err([g.astype(jnp.float8_e4m3fn)
                                    for g in got])
    finally:
        if release is not None:
            release()
    return errs, (lo, served)
