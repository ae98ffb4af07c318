"""A Mellum-family model behind ``serving.InferenceEngine`` ->
``DecodeScheduler`` (``paddle_tpu/models/mellum.py``): the builders, the checks
against the plain reference at the configuration's own shapes, and the bytes
and operations a perfect decode step must move.  Every size comes from the
configuration's file (the family's own key names)."""
from __future__ import annotations

import collections

import numpy as np

# THE LIMITS OF ``correct``, each with what it holds and its two readings (my
# chip runs, PR 35; the table in PERF.md section 6).  Which limit fails a
# LOWER PRECISION than the configuration states: ``SERVED_STATE_TOL``'s
# ``kv_rows`` (an 8-bit K/V row) and ``routing_mismatch`` (bfloat16 router
# scores).  Which fails a WRONG MECHANISM: ``window_decode`` /
# ``window_prefill`` (a window of 1023 or 1025), ``kv_rows_deep`` (plain
# rotary in a full layer, a missing ``attention_factor``).  The readings of
# each variant are taken in every run beside the sound one (``NOT_JUDGED``).
#
# Each mechanism stand-alone against the plain reference (float32, highest
# precision) at the configuration's own shapes, max |a - b| / max |b|:
#   full_decode / window_decode / full_prefill / window_prefill: the walk over
#     bfloat16 pools (grouped heads, 512 keys a turn; the window's first page
#     masked, its table a ring) against the reference's masked attention over
#     the same bfloat16 rows, slots at ``kv_len`` under, at and far over the
#     window.  Served 1.2e-7 to 2.3e-7 (decode) and 1.0e-6 to 3.5e-6
#     (prefill): both products run over exact bfloat16 parts, so nothing is
#     rounded that the reference does not round.  The same kernel at a window
#     of 1023 / 1025 reads 7.1e-3 to 3.0e-2 (decode: one key of 1024 in a few
#     slots) and 0.11 to 0.42 (prefill), ``window_*_w1023`` / ``_w1025``; a
#     dropped or misaddressed page, a wrong first page or scale 0.05 or more.
#     The limits are a seventh of the least wrong reading over 19 runs and
#     hundreds of times the sound.
#   moe_decode / moe_prefill: ``moe_topk(scoring="softmax")`` (sort, grouped
#     product, weights) at a decode step's and a chunk's rows against the
#     reference's masked loop over all experts, the served weights of layer 0.
#     Served 1.4e-3 to 2.2e-3 (bfloat16 operands); a dropped pair or weights
#     that are not renormalised read 0.05 or more.
#   routing_mismatch: the share of (row, expert) entries on which the served
#     router's chosen sets differ from the reference's, from the SAME float32
#     rows.  Served 0.0; logits from bfloat16 operands 5.1e-3 to 9.0e-3
#     (``routing_mismatch_bf16``, read in every run beside it).
MECHANISM_RTOL = {"full_decode": 1e-3, "window_decode": 1e-3,
                  "full_prefill": 2e-3, "window_prefill": 2e-3,
                  "moe_decode": 8e-3, "moe_prefill": 8e-3,
                  "routing_mismatch": 2e-3}
ROUTED_ROWS = 1024      # rows the router alone is read on
NOT_JUDGED = ("routing_mismatch_bf16", "kv_rows_8bit",
              "window_decode_w1023", "window_decode_w1025",
              "window_prefill_w1023", "window_prefill_w1025",
              "kv_rows_deep_unforced", "kv_rows_deep_median",
              "kv_rows_deep_max", "k_rows_plain_rotary",
              "k_rows_no_attention_factor", "window_pages_reused")
# TOP-8 IS A DISCRETE CHOICE (PR 33's finding for top-6 holds): the logits are
# compared OVER THE SAME EXPERTS (the reference's ``forced``), the choice
# itself apart, and the served tokens are held to the reference in their
# SHARE.  ``models/deepseek_v3.py`` has the reasoning of each limit; read
# here: logits 0.036 to 0.058 standard deviations from the reference's
# (``LOGIT_TOL``), 0.969 to 1.0 of 128 served tokens within ``TIE_TOL`` of
# its top (``TOKENS_AGREE``), 0.9937 to 0.9959 of its experts chosen too
# (``ROUTING_AGREE``); a wrong mechanism, another model's logits, reads 0.0.
LOGIT_TOL = 0.1
TIE_TOL = 0.15
CHECKED_TOKENS = 128
TOKENS_AGREE = 0.7
ROUTING_AGREE = 0.95


def make_params(cfg, seed):
    from paddle_tpu import observability as obs
    from paddle_tpu.models import mellum as M

    with obs.span("serving.model_load", model="mellum-weights"):
        import jax

        params = M.params(cfg, seed, dtype=cfg["weights_dtype"])
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
    from paddle_tpu.models import mellum as M

    return serving.InferenceEngine(
        decode_model=M.build_decode_model(params, cfg),
        decode_config=decode_config(cfg, max_new_tokens))


def _rel(a, b):
    a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
    if not np.all(np.isfinite(a)):
        return float("inf")
    return float(np.max(np.abs(a - b)) / np.max(np.abs(b)))


def _chosen_mask(experts, n):
    """``[T, k]`` expert ids -> ``[T, n]`` bool."""
    experts = np.asarray(experts)
    mask = np.zeros((experts.shape[0], n), bool)
    np.put_along_axis(mask, experts, True, axis=1)
    return mask


def mechanism_errors(cfg, params, seed, reference):
    """The mechanisms as the step programs call them (the engine the program
    picks here) against the plain reference at the configuration's head
    counts, widths, page size, slots, chunk and window, on seeded random
    inputs and the served weights of layer 0."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.models import mellum as M
    from paddle_tpu.parallel import flash_attention as FA
    from paddle_tpu.parallel import moe

    d = M._dims(cfg)
    H, Hkv, Dh, W = d["H"], d["Hkv"], d["Dh"], d["W"]
    ps, C, S = cfg["page"], cfg["chunk"], cfg["slots"]
    T = min(9 * C + 3 * ps + 5, cfg["max_seq_len"] - C)     # ragged
    T = max(T, min(W + 3 * ps + 5, cfg["max_seq_len"] - C))
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

    # decode: slots from one key to the whole pool, some at the window's
    # edge, one empty
    lens = np.linspace(1, T, S).astype(np.int32)
    lens[S // 2] = 0
    for i, n in enumerate((W - 1, W, W + 1, W + ps, min(T, 4 * W + 3))):
        if i + 1 < S and 0 < n <= T:
            lens[i + 1] = n
    live = lens > 0
    q = jax.random.normal(ks[2], (S, H, Dh), jnp.float32).astype(act)
    tables = jnp.broadcast_to(perm[None, :], (S, npg))
    want = {w: np.asarray(plain(
        q.astype(jnp.float32), k_all, v_all,
        jnp.asarray(np.maximum(lens - 1, 0)), w)) for w in (None, W)}

    def decode(window):
        return np.asarray(jax.jit(
            lambda q, k, v, t, n: FA.paged_gqa_decode_attention(
                q, k, v, t, n, layer=0, window=window,
                sm_scale=d["sm_scale"]))(q, k_pool, v_pool, tables,
                                         jnp.asarray(lens)))

    for name, window, ref_w in (("full_decode", None, None),
                                ("window_decode", W, W),
                                ("window_decode_w1023", W - 1, W),
                                ("window_decode_w1025", W + 1, W)):
        got = decode(window)
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
    for name, window, ref_w in (("full_prefill", None, None),
                                ("window_prefill", W, W),
                                ("window_prefill_w1023", W - 1, W),
                                ("window_prefill_w1025", W + 1, W)):
        got = jax.jit(lambda q, k, v, pages: FA.paged_gqa_prefill_attention(
            q, k, v, pages, jnp.int32(start), jnp.int32(valid), layer=0,
            window=window, sm_scale=d["sm_scale"]))(qc, k_pool, v_pool, perm)
        errs[name] = _rel(np.asarray(got)[:valid], want[ref_w])
    del k_pool, v_pool, k_all, v_all

    # the expert layer at a decode step's and at a chunk's rows
    def served(p, u):
        return moe.moe_topk(
            u.astype(act), {"w": p["router_w"][0], "bias": None},
            {"w_gu": p["e_gu"], "w_down": p["e_down"]}, None, top_k=d["k"],
            experts_held=(0, d["E"]), scoring="softmax", layer=0)[0]

    def loop(p, u):
        return reference.moe_layer(u, p["router_w"][0], p["e_gu"][0],
                                   p["e_down"][0], d["k"])[0]

    served, loop = jax.jit(served), jax.jit(loop)
    for name, n, key in (("moe_decode", S, ks[4]), ("moe_prefill", C, ks[5])):
        u = jax.random.normal(key, (n, d["D"]), jnp.float32)
        u = u.astype(act).astype(jnp.float32)       # the same rows both sides
        errs[name] = _rel(served(params, u), loop(params, u))
    # the router alone, from the same float32 rows on both sides
    u = jax.random.normal(ks[6], (ROUTED_ROWS, d["D"]), jnp.float32)
    w = params["router_w"][0]
    want = np.asarray(jax.jit(lambda u, w: reference.route(
        u, w, d["k"])[0])(u, w))
    for name, route in (
            ("routing_mismatch", lambda x, w: moe.route_topk(
                x, w, None, top_k=d["k"], scoring="softmax")[0]),
            ("routing_mismatch_bf16", lambda x, w: _route_bf16(
                x, w, d["k"]))):
        got = _chosen_mask(jax.jit(route)(u, w), d["E"])
        errs[name] = float((got != want).sum() / want.sum())
    errs.update(rotary_variants(cfg, seed))
    return errs


def _route_bf16(x, w, top_k):
    """The experts a router would choose whose logits come from bfloat16
    operands and are kept in bfloat16: the lower precision's reading."""
    import jax
    import jax.numpy as jnp

    logits = jnp.dot(x.astype(jnp.bfloat16), w.astype(jnp.bfloat16),
                     preferred_element_type=jnp.float32)
    return jax.lax.top_k(jax.nn.softmax(
        jax.lax.reduce_precision(logits, 8, 7), axis=-1), top_k)[1]


def rotary_variants(cfg, seed, rows=256):
    """What a WRONG rotary in a full layer would read in ``kv_rows_deep``'s
    own measure (a K row's distance from the reference's, in the row's
    norm): the least over ``rows`` random rows at positions spread over the
    served range, with plain rotary (the sliding layers' ``rope_parameters``)
    and with YaRN's frequencies but no ``attention_factor``.  Both must lie
    past ``DEEP_ROW_TOL``; judged by nothing, read in every run."""
    from paddle_tpu.models import mellum as M

    rng = np.random.RandomState((seed + 11) % (2 ** 32))
    half = cfg["head_dim"] // 2
    pos = rng.randint(0, cfg["max_seq_len"], size=rows).astype(np.float64)
    k = rng.standard_normal((rows, cfg["head_dim"]))
    rope = cfg["rope_parameters"]

    def rotated(inv_freq, factor):
        ang = pos[:, None] * inv_freq.astype(np.float64)
        cos, sin = np.cos(ang) * factor, np.sin(ang) * factor
        return np.concatenate([k[:, :half] * cos - k[:, half:] * sin,
                               k[:, half:] * cos + k[:, :half] * sin], axis=1)

    inv, factor = M.rope_inverse_frequencies(rope["full_attention"],
                                             cfg["head_dim"])
    plain, _ = M.rope_inverse_frequencies(rope["sliding_attention"],
                                          cfg["head_dim"])
    want = rotated(inv, factor)

    def least(got):
        return float((np.linalg.norm(got - want, axis=1)
                      / np.linalg.norm(want, axis=1)).min())

    return {"k_rows_plain_rotary": least(rotated(plain, 1.0)),
            "k_rows_no_attention_factor": least(rotated(inv, 1.0))}


def reference_logits(cfg, params, sequence, positions, reference,
                     forced=None):
    """The reference's next-token logits ``[P, V]`` at ``positions`` of
    ``sequence``, each layer's own chosen experts there ``[P, E]`` and each
    layer's K and V rows there ``[P, 2, Hkv * head_dim]``.  ``forced = (rows,
    [sets [F, E] per layer])``: the experts those rows are computed over (the
    reference's ``forced``).  The sequence is padded to the configuration's
    ``max_seq_len``, the positions to whole chunks and the forced rows to the
    most a replay has, each by repeating its last: one compiled program for
    most lengths."""
    import jax
    import jax.numpy as jnp

    # 32 query rows a block: scores and probabilities against 36864 keys are
    # 151 MB each (at 64 rows the check's temporaries were 2.7 GB beside the
    # weights: my chip run, PR 35)
    block = 32 if cfg["max_seq_len"] % 32 == 0 else cfg["page"]
    seq = np.zeros(-(-cfg["max_seq_len"] // block) * block, np.int32)
    seq[:len(sequence)] = sequence
    n, C = len(positions), cfg["chunk"]
    positions = list(positions) + [positions[-1]] * (-n % C)
    if forced is not None:
        rows, sets = forced
        pad = C + 1 + N_DECODE - len(rows)
        forced = (jnp.asarray(list(rows) + [rows[-1]] * pad, jnp.int32),
                  [jnp.asarray(np.concatenate([s] + [s[-1:]] * pad))
                   for s in sets])
    key = (id(reference), len(positions), forced is not None)
    fn = _REFERENCE_FN.get(key)
    if fn is None:
        fn = _REFERENCE_FN[key] = jax.jit(
            lambda p, s, q, f: reference.forward(p, cfg, s, q, block=block,
                                                 forced=f))
    logits, chosen, rows = fn(params, jnp.asarray(seq),
                              jnp.asarray(positions, jnp.int32), forced)
    return (np.asarray(logits[:n], np.float64),
            [np.asarray(c)[:n] for c in chosen],
            [np.stack([np.asarray(k)[:n], np.asarray(v)[:n]], axis=1)
             for k, v in rows])


_REFERENCE_FN = {}


def gap(logits, token):
    """How far ``token`` sits below the top of ``logits``, in their standard
    deviations (0 where it is the top)."""
    return float((logits.max() - logits[int(token)]) / logits.std())


# ONE SCHEDULE, RUN TWICE over a checked sequence (as ``models/deepseek_v3.py``
# does): through the engine's OWN compiled step programs into the engine's OWN
# cache after the drain (:func:`served_state_errors`, which reads the K and V
# leaves of both groups they leave), and through the step FUNCTIONS under a
# ``jax.jit`` that also returns their logits and the experts ``moe_topk``
# chose (:func:`replay`), on a cache of the cell's size.  The same tokens,
# pages and tables both times: ``sequence[:n]`` in chunks of ``chunk`` (``n``
# = ``split`` floored to a page), token ``n`` through the narrowest chunk
# program, then ``N_DECODE`` tokens decoded in slot 0 while every other slot
# decodes random tokens on a page of its own.  The WINDOW group's pages are
# handed out and given back as the scheduler does it (a page a logical page as
# the positions reach it, back to the free list when every position on it is
# out of the next position's window, the table a ring), with all but a few
# of the group's free pages held aside, so that the pages a long sequence
# takes late are ones it gave back early.
N_DECODE = 4


def _schedule(cfg, cache, sequence, split, seed, chunk, decode):
    """Run the schedule above: ``chunk(width, tokens, start, valid, {group:
    pages written}, {group: table row})`` and ``decode(tokens, positions,
    {group: tables}, kv_lens)`` are the two programs.  Returns ``(release,
    first, end, chunk results, decode results, where)``: rows ``first .. end
    - 1`` are the last whole-width chunk's, the narrow chunk's and the
    decoded ones; ``where`` = ``(full pages, first live window page, its
    pages from there on, window pages taken twice)``; ``release()`` frees
    everything."""
    import jax.numpy as jnp

    S, ps, C = cfg["slots"], cfg["page"], cfg["chunk"]
    narrow = min(b for b in list(cfg["buckets"]) + [C] if b <= C)
    n = (min(split, len(sequence) - 1 - N_DECODE) // ps) * ps
    assert n > 0, "a checked sequence is a page and %d tokens" % (N_DECODE + 1)
    end = n + 1 + N_DECODE
    full, win = cache.primary_group, "window"
    grp = cache.groups[win]
    width = grp.slot_bound(cfg["max_seq_len"], C)
    pages = cache.alloc(cache.pages_for(end))
    rest = [cache.alloc(1)[0] for _ in range(S - 1)]
    rest_w = grp.alloc(S - 1)
    aside = grp.alloc(max(0, grp.free_pages - width - 2))
    tables = np.zeros((S, cache.max_pages_per_seq), np.int32)
    tables[0] = cache.table_row(pages)
    tables[1:, 0] = rest
    ring = np.zeros((S, width), np.int32)
    ring[1:, 0] = rest_w
    held, base, seen, again = collections.deque(), [0], set(), [0]
    rng = np.random.RandomState(seed % (2 ** 32))

    def device(a):
        # a copy: the programs run behind the host, which rewrites the ring
        # (the CPU backend reads a numpy buffer in place)
        return jnp.asarray(a.copy())

    def reach(upto):
        for p in range(base[0] + len(held), -(-upto // ps)):
            page = grp.alloc(1)[0]
            again[0] += page in seen
            seen.add(page)
            held.append(page)
            ring[0, p % width] = page

    def leave(next_pos):
        live = grp.first_live_page(next_pos)
        while base[0] < live and held:
            ring[0, base[0] % width] = 0
            grp.free([held.popleft()], released=True)
            base[0] += 1

    def one(w, start, valid):
        reach(start + valid)
        tokens = np.zeros(w, np.int32)
        tokens[:valid] = sequence[start:start + valid]
        vec, vec_w = np.zeros(w // ps, np.int32), np.zeros(w // ps, np.int32)
        m = max(0, min(w // ps, len(pages) - start // ps))
        vec[:m] = pages[start // ps:start // ps + m]
        for i in range(min(w // ps, -(-(start + valid) // ps) - start // ps)):
            vec_w[i] = ring[0, (start // ps + i) % width]
        out = chunk(w, jnp.asarray(tokens), jnp.int32(start),
                    jnp.int32(valid),
                    {full: jnp.asarray(vec), win: jnp.asarray(vec_w)},
                    {full: jnp.asarray(tables[0]), win: device(ring[0])})
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
            {full: jnp.asarray(tables), win: device(ring)},
            jnp.asarray(positions + 1)))
        leave(pos + 1)

    def release():
        cache.free(pages + rest)
        grp.free(list(held) + rest_w + aside)

    where = (pages, base[0], list(held), again[0])
    return release, max(0, ((n - 1) // C) * C), end, chunks, steps, where


def replay_fns(cfg):
    """The two step functions under a ``jax.jit`` of their own that also
    returns the routing: made once a run, so that every checked request
    replays through the same executables."""
    import jax

    from paddle_tpu.models import mellum as M

    donate = () if jax.default_backend() == "cpu" else (1,)
    return (jax.jit(lambda p, c, *a: M.prefill_chunk(
                p, *a[:3], c, *a[3:], cfg=cfg, with_routing=True),
                donate_argnums=donate),
            jax.jit(lambda p, c, *a: M.decode_step(
                p, *a[:2], c, *a[2:], cfg=cfg, with_routing=True),
                donate_argnums=donate))


def fresh_cache(cfg):
    """A cache of the cell's size and groups, as the scheduler builds it."""
    from paddle_tpu import serving
    from paddle_tpu.models import mellum as M

    layout = M.cache_layout(cfg)
    groups = {g: dict(spec, num_pages=cfg["num_pages"][g])
              for g, spec in layout["page_groups"].items()}
    return serving.PagedKVCache(
        0, None, cfg["page"], 0, 0, cfg["max_seq_len"],
        dtype=cfg["kv_dtype"], num_slots=cfg["slots"],
        page_pools=layout["page_pools"], page_groups=groups)


def replay(cfg, params, sequence, split, seed, fns):
    """The step functions' own LOGITS and ROUTING on the schedule above
    (``fns`` from :func:`replay_fns`, a fresh cache of the cell's size).
    Returns ``(logits [2 + N_DECODE, V] at positions n - 1 .. end - 1, sets,
    first, end)``: ``sets`` one ``[end - first, E]`` bool mask per layer, the
    experts ``moe_topk`` computed rows ``first .. end - 1`` over."""
    import jax.numpy as jnp

    cache = fresh_cache(cfg)
    pools = [cache.pools]
    n_exp = cfg["num_experts"]

    def chunk(width, tokens, start, valid, written, rows):
        logits, pools[0], routing = fns[0](
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

    _, first, end, chunks, steps, _ = _schedule(
        cfg, cache, sequence, split, seed, chunk, decode)
    outs = chunks[-2:] + steps
    sets = [np.concatenate(layer) for layer in zip(*(o[1] for o in outs))]
    return np.stack([o[0] for o in outs]), sets, first, end


def routing_agreement(served, reference_chosen):
    """Mean share of the reference's chosen experts that the served router
    chose too, over rows (``[rows, E]`` bool each), and the share of rows
    whose sets are equal."""
    both = (served & reference_chosen).sum(axis=1)
    want = np.maximum(reference_chosen.sum(axis=1), 1)
    return float((both / want).mean()), float(
        (served == reference_chosen).all(axis=1).mean())


# THE K AND V ROWS HELD ON THE OBJECT THAT IS TIMED (the engine's own
# executables on the engine's own cache, the schedule above, window pages
# released and taken again on the way: ``window_pages_reused`` counts them).
#   kv_rows: layer 0's K and V rows (a sliding layer: the window group's
#     leaves) at every position still live at the end, the ones chunks wrote
#     and the ones decode steps wrote alike.  A first layer's row depends on
#     its token and position alone (``rotary(norm1(E[tok]) W_k)``, ``..
#     W_v``), so the reference gives it without the cache, in float32: max
#     |row - reference| / max |reference| over K and V.  The same rows kept in
#     8 bits (float8 e4m3: ``kv_rows_8bit``, read beside it in every run from
#     the served leaf rounded once more) is the lower precision's reading; the
#     limit sits between them in the logarithm.
#   kv_rows_deep: the rows of every LATER layer (the full group's layers 3 and
#     7 among them) at positions ``first .. end - 1`` (a sliding layer's from
#     its first live page on).  A later layer's row is a function of the
#     experts its token took in the layers before, so the reference computes
#     these rows over the experts the step functions' replay reports
#     (``forced``): the share of (row, layer, K | V) entries whose distance
#     from the reference's row, in the row's own norm, is past
#     ``DEEP_ROW_TOL``.  It holds the ENGINE'S executables to the routing the
#     replay reports and to the rotary of each kind: plain rotary in a full
#     layer or a missing ``attention_factor`` puts EVERY K row of a full layer
#     0.2 or more away (``k_rows_plain_rotary``, ``k_rows_no_attention_
#     factor``: the least such distance, 0.218 and 0.217).  Served 0.0 of the
#     entries: a sound row lies 6.0e-3 (median) to 1.15e-2 (the largest) from
#     the reference's, and ``DEEP_ROW_TOL`` is 2.6 times that; the chunk
#     before, which the reference routes by itself, reads 0.10 to 0.14
#     (``kv_rows_deep_unforced``): what routing apart reads.
#   window_pages_left: pages in use or reserved in the window group when the
#     check begins, after the cancel and the drain (the driver's own wait reads
#     the first group's pages): 0.
SERVED_STATE_TOL = {"kv_rows": 1.4e-2, "kv_rows_deep": 1e-2,
                    "window_pages_left": 0.0}
DEEP_ROW_TOL = 0.03


def served_state_errors(cfg, scheduler, sequence, split, seed, params,
                        reference):
    """``SERVED_STATE_TOL``'s first-layer readings from ``scheduler``'s own
    programs and cache (stopped, every page free), and for
    :func:`deep_row_errors` the rows they left in every layer at positions
    ``lo ..`` (a sliding layer's no earlier than its first live page):
    ``(errs, (lo, [(at, k rows, v rows) per layer]))``."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.models import mellum as M

    d = M._dims(cfg)
    cache, ps = scheduler.cache, cfg["page"]
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
            _, kn, vn = M.GROUPS[kind]
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
        _, kn, vn = M.GROUPS[kind]
        at = 0 if kind == "full_attention" else live * ps
        ids = pages[:cache.pages_for(end)] if kind == "full_attention" else held
        got = [rows(leaf, 0, ids, at) for leaf in (kn, vn)]
        want = jax.jit(lambda p, t: reference.layer_rows(
            p, cfg, 0, p["embed"][t].astype(jnp.float32),
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


def deep_row_errors(cfg, first, served, reference_rows):
    """``kv_rows_deep`` (rows ``first ..``, which the reference computed over
    the replay's experts; their median and largest distance beside it) and
    ``kv_rows_deep_unforced`` (the rows before them) from ``served = (lo, [(at,
    k, v) per layer])`` and the reference's rows per layer at positions ``lo
    ..`` (``[n, 2, width]``: K and V)."""
    lo, layers = served
    far_forced, far_before = [], []
    for (at, *got), want in zip(layers[1:], reference_rows[1:]):
        for which, g in enumerate(got):
            if not np.all(np.isfinite(g)):
                return {"kv_rows_deep": float("inf")}
            w = np.asarray(want, np.float64)[at - lo:, which]
            far = (np.linalg.norm(g - w, axis=-1)
                   / np.maximum(np.linalg.norm(w, axis=-1), 1e-30))
            cut = max(0, first - at)
            far_forced.append(far[cut:])
            far_before.append(far[:cut])
    forced = np.concatenate(far_forced)
    errs = {"kv_rows_deep": float((forced > DEEP_ROW_TOL).mean()),
            "kv_rows_deep_median": float(np.median(forced)),
            "kv_rows_deep_max": float(forced.max())}
    before = np.concatenate(far_before)
    if before.size:
        errs["kv_rows_deep_unforced"] = float((before > DEEP_ROW_TOL).mean())
    return errs


# -- what a perfect decode step must move -------------------------------------

def _item(cfg):
    return 2 if cfg["weights_dtype"] == "bfloat16" else 4


def expert_params(cfg):
    """Parameters of ONE routed expert (gate, up, down)."""
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def weight_bytes(cfg):
    """Bytes of weights EVERY decode step reads whatever it routes: each
    layer's attention matrices, the routers (float32) and the head; of the
    embedding only the rows looked up.  The experts are :func:`expert_bytes`."""
    D, Dh = cfg["hidden_size"], cfg["head_dim"]
    H, Hkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    L = cfg["num_hidden_layers"]
    attn = D * (H + 2 * Hkv) * Dh + H * Dh * D
    n = L * attn + D * cfg["vocab_size"] + cfg["slots"] * D
    return _item(cfg) * n + 4 * L * D * cfg["num_experts"]


def expert_bytes(cfg, experts_touched):
    """Bytes of expert weights a step reads: the experts that took a pair,
    summed over the layers (``serving.decode.moe.experts_touched`` a step)."""
    return _item(cfg) * expert_params(cfg) * experts_touched


def kv_bytes(cfg, full_tokens, window_tokens):
    """``(full, window)`` bytes of K and V rows a step's attention reads:
    every position each kind of layer is entitled to, of every slot
    (``serving.decode.kv.full_tokens_read`` / ``.window_tokens_read`` a
    step), a K and a V row of ``Hkv * head_dim`` values each.  The rows as
    the model defines them, not the whole pages the walk copies."""
    kv = 2 if cfg["kv_dtype"] == "bfloat16" else 4
    row = 2 * cfg["num_key_value_heads"] * cfg["head_dim"] * kv
    return row * full_tokens, row * window_tokens


def moe_flops(cfg, pairs):
    """Operations of the experts in one step: a pair is one token through one
    expert's three matrices."""
    return 2 * expert_params(cfg) * pairs
