"""A DeepSeek-V3-family model behind ``serving.InferenceEngine`` ->
``DecodeScheduler`` (``paddle_tpu/models/deepseek_v3.py``): the builders, the
checks against the plain reference at the configuration's own shapes, and the
bytes and operations a perfect decode step must move.  Every size comes from
the configuration's file (the family's own key names)."""
from __future__ import annotations

import numpy as np

# THE LIMITS OF ``correct``, each with what it holds and its two readings (my
# chip runs, PR 33; the table in PERF.md section 6).  Which limit fails a
# LOWER PRECISION than the configuration states: ``SERVED_STATE_TOL``'s
# ``latent_rows`` (an 8-bit latent) and ``routing_mismatch`` (bfloat16 router
# scores).  The others hold the path against a WRONG mechanism and say so.
#
# Each mechanism stand-alone against the plain reference (float32, highest
# precision) at the configuration's own shapes, max |a - b| / max |b|:
#   mla_decode / mla_prefill: the absorbed kernel over a bfloat16 latent pool
#     (the query carried through W_uk in the activations' dtype, ``P c``
#     through W_uv) against the reference's EXPANDED attention over the same
#     rows.  Served 3.3e-4 to 1.2e-3 and 2.1e-3 to 5.2e-3 (bfloat16 operands
#     of three chained products; a chunk's early rows see few keys and
#     average less); a page dropped or misaddressed, a wrong rotary pairing
#     or scale reads 0.1 or more.  Four and three times the largest.
#   moe_decode / moe_prefill: ``moe_topk`` (sort, grouped product, weights,
#     shared experts) at a decode step's and a chunk's rows against the
#     reference's masked loop over all experts, the served weights of the
#     first expert layer.  Served 1.4e-3 to 1.9e-3; a dropped pair, a weight
#     with the bias in it or without the 2.448 reads 0.05 or more.
#   routing_mismatch: the share of (row, expert) entries on which the served
#     router's chosen sets differ from the reference's, from the SAME float32
#     rows (differing entries / chosen entries).  Served 0.0 (float32 scores
#     at the highest precision, the reference's own arithmetic); scores from
#     bfloat16 operands 1.5e-2 to 2.5e-2 (``routing_mismatch_bf16``, read in
#     every run beside it and judged by nothing).
MECHANISM_RTOL = {"mla_decode": 5e-3, "mla_prefill": 1.5e-2,
                  "moe_decode": 8e-3, "moe_prefill": 8e-3,
                  "routing_mismatch": 2e-3}
ROUTED_ROWS = 1024      # rows the router alone is read on
# readings that are there for the record (the lower precision's) and judged
# by nothing
NOT_JUDGED = ("routing_mismatch_bf16", "latent_rows_8bit",
              "latent_rows_deep_unforced", "latent_rows_deep_median",
              "latent_rows_deep_max")
# TOP-6 IS A DISCRETE CHOICE.  A served row whose sixth and seventh biased
# scores lie closer than the bfloat16 rounding of its residual stream takes
# another expert than the float32 reference (0.948 to 0.968 of the (row,
# expert layer) sets are equal), and a row with one other expert of six in
# any of five layers has logits 0.7 to 2.3 standard deviations from the
# reference's (the first run of this PR, before the comparison said so).  So
# the logits are compared OVER THE SAME EXPERTS (the reference's ``forced``),
# the choice itself is compared apart, and the served tokens are held to the
# reference in their SHARE, not each.
#
# next-token LOGITS of the step FUNCTIONS (a second ``jax.jit`` of
# ``prefill_chunk`` / ``decode_step`` that also returns the experts
# ``moe_topk`` computed with, on a cache of the cell's size: :func:`replay`)
# against the float32 reference computing the replayed rows over those
# experts, max |a - b| over the vocabulary in standard deviations of the
# reference's logits, at the last whole chunk's last row, the narrow chunk's
# token and every decoded token: 0.037 to 0.054.  What ties that second jit
# to the engine's executables is ``latent_rows_deep`` below.  It holds the whole path (latent cache, chunking, absorbed
# attention, experts) against a wrong mechanism; it does not tell a lower
# precision: bfloat16 weights and activations are the error.
LOGIT_TOL = 0.1
# a served token counts as the reference's where the reference (its own
# routing) puts it within TIE_TOL standard deviations of its top logit (two
# logits each off by up to 0.05 can swap when 0.1 apart); CHECKED_TOKENS
# served tokens of a checked request, evenly spaced, are read, and
# TOKENS_AGREE of them must count: 0.87 to 0.96 read (the farthest of those
# that do not count 0.8 to 2.6 deviations down); a wrong mechanism, whose
# logits are another model's, reads 0.0 (one token of the vocabulary by
# chance).  The limit is six standard deviations of a share of 128 under
# the lowest reading.
TIE_TOL = 0.15
CHECKED_TOKENS = 128
TOKENS_AGREE = 0.7
# mean share of the reference's chosen experts, over the (token, expert layer)
# pairs of the replayed rows (the last whole chunk's, the narrow chunk's, the
# decoded tokens'), that ``moe_topk`` chose too (its input is the bfloat16 residual stream, the
# reference's float32: a near tie at the sixth place swaps one expert of six;
# 0.9914 to 0.9971 read, each layer's rows over the experts the served
# layers before it chose).  It holds a wrong RULE (the bias in the weights does
# not show here, a missing bias or a softmax does: under 0.8), not a precision:
# the limit sits in the upper half of the gap between the two readings.
ROUTING_AGREE = 0.95


def make_params(cfg, seed):
    from paddle_tpu import observability as obs
    from paddle_tpu.models import deepseek_v3 as M

    with obs.span("serving.model_load", model="deepseek-v3-weights"):
        import jax

        params = M.params(cfg, seed, dtype=cfg["weights_dtype"])
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
    from paddle_tpu.models import deepseek_v3 as M

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
    counts, widths, page size, slots and chunk, on seeded random inputs and
    the served weights of the first layers."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.models import deepseek_v3 as M
    from paddle_tpu.parallel import flash_attention as FA
    from paddle_tpu.parallel import moe

    d = M._dims(cfg)
    H, dn, dr, R, W = d["H"], d["dn"], d["dr"], d["R"], d["W"]
    ps, C, S = cfg["page"], cfg["chunk"], cfg["slots"]
    T = min(9 * C + 3 * ps + 5, cfg["max_seq_len"] - C)     # ragged
    npg = -(-(T + C) // ps)
    ks = jax.random.split(jax.random.PRNGKey((seed + 5) % (2 ** 31)), 8)
    kv_dt = jnp.dtype(cfg["kv_dtype"])
    act = params["embed"].dtype
    # every array goes into a jitted function as an ARGUMENT (a closed-over
    # array is a constant of the program)
    wkvb = params["layers"][0]["wkvb"]
    rows = jnp.concatenate([
        jax.random.normal(ks[0], (npg * ps, R + dr), jnp.float32),
        jnp.zeros((npg * ps, W - R - dr), jnp.float32)], axis=1).astype(kv_dt)
    perm = 1 + jax.random.permutation(ks[1], npg).astype(jnp.int32)
    pool = jnp.zeros((1, npg + 1, ps, W), kv_dt).at[0, perm].set(
        rows.reshape(npg, ps, W))
    k_all, v_all = jax.jit(lambda r, w: reference.expand_latent(
        r[:, :R], r[:, R:R + dr], w, dn))(rows.astype(jnp.float32), wkvb)
    errs = {}

    def absorbed(q, w):
        """``[.., H, dn + dr]`` float32 queries as the kernels take them."""
        q_lat = jnp.einsum("thd,hdc->thc", q[..., :dn].astype(act),
                           w[:, :dn, :], preferred_element_type=jnp.float32)
        return jnp.concatenate([q_lat, q[..., dn:], jnp.zeros(
            q.shape[:2] + (W - R - dr,), jnp.float32)], axis=-1).astype(act)

    def heads(o, w):
        return jnp.einsum("thc,hdc->thd", o.astype(act), w[:, dn:, :],
                          preferred_element_type=jnp.float32)

    # decode: every slot at its own length, one empty
    lens = np.linspace(1, T, S).astype(np.int32)
    lens[S // 2] = 0
    q = jax.random.normal(ks[2], (S, H, dn + dr), jnp.float32)
    tables = jnp.broadcast_to(perm[None, :], (S, npg))
    got = jax.jit(lambda q, pool, w, t, n: heads(
        FA.paged_mla_decode_attention(
            absorbed(q, w), pool, t, n, v_width=R, sm_scale=d["sm_scale"],
            layer=0), w))(q, pool, wkvb, tables, jnp.asarray(lens))
    want = jax.jit(reference.attention)(
        q, k_all, v_all, jnp.asarray(np.maximum(lens - 1, 0)))
    live = lens > 0
    errs["mla_decode"] = _rel(np.asarray(got)[live], np.asarray(want)[live])
    if np.asarray(got)[~live].any():
        errs["mla_decode_empty_slot_not_zero"] = float("inf")

    # prefill: one ragged chunk late in the sequence
    start = ((T - C) // ps) * ps
    valid = C - max(1, C // 14)
    qc = jax.random.normal(ks[3], (C, H, dn + dr), jnp.float32)
    got = jax.jit(lambda q, pool, w, pages: heads(
        FA.paged_mla_prefill_attention(
            absorbed(q, w), pool, pages, jnp.int32(start), jnp.int32(valid),
            v_width=R, sm_scale=d["sm_scale"], layer=0), w))(
                qc, pool, wkvb, perm)
    want = jax.jit(reference.attention)(
        qc, k_all, v_all, start + jnp.arange(C, dtype=jnp.int32))
    errs["mla_prefill"] = _rel(np.asarray(got)[:valid],
                               np.asarray(want)[:valid])
    del pool, k_all, v_all

    # the expert layer at a decode step's and at a chunk's rows
    if d["L"] > d["n_dense"]:
        def layer_weights(p):
            lp = p["layers"][d["n_dense"]]
            return ({"w": p["router_w"][0], "bias": p["router_b"][0]},
                    {"w_gu": lp["w_gu"], "w_down": lp["w_down"]})

        def served(p, u):
            router, shared = layer_weights(p)
            return moe.moe_topk(
                u.astype(act), router,
                {"w_gu": p["e_gu"], "w_down": p["e_down"]}, shared,
                top_k=d["k"], experts_held=(0, d["E"]), scale=d["scale"],
                layer=0)[0]

        def plain(p, u):
            router, shared = layer_weights(p)
            return reference.moe_layer(
                u, router["w"], router["bias"], p["e_gu"][0], p["e_down"][0],
                (shared["w_gu"], shared["w_down"]), d["k"], d["scale"])

        served, plain = jax.jit(served), jax.jit(plain)
        for name, n, key in (("moe_decode", S, ks[4]),
                             ("moe_prefill", C, ks[5])):
            u = jax.random.normal(key, (n, d["D"]), jnp.float32)
            u = u.astype(act).astype(jnp.float32)   # the same rows both sides
            errs[name] = _rel(served(params, u), plain(params, u)[0])
        # the router alone, from the same float32 rows on both sides
        u = jax.random.normal(ks[6], (ROUTED_ROWS, d["D"]), jnp.float32)
        w, bias = params["router_w"][0], params["router_b"][0]
        want = np.asarray(jax.jit(lambda u, w, b: reference.route(
            u, w, b, d["k"], d["scale"])[0])(u, w, bias))
        for name, route in (
                ("routing_mismatch", lambda x, w, b: moe.route_topk(
                    x, w, b, top_k=d["k"])[0]),
                ("routing_mismatch_bf16", lambda x, w, b: _route_bf16(
                    x, w, b, d["k"]))):
            got = _chosen_mask(jax.jit(route)(u, w, bias), d["E"])
            errs[name] = float((got != want).sum() / want.sum())
    return errs


def _route_bf16(x, w, bias, top_k):
    """The experts a router would choose whose scores come from bfloat16
    operands and are kept in bfloat16: the lower precision's reading."""
    import jax
    import jax.numpy as jnp

    scores = jax.nn.sigmoid(jnp.dot(
        x.astype(jnp.bfloat16), w.astype(jnp.bfloat16),
        preferred_element_type=jnp.float32))
    return jax.lax.top_k(jax.lax.reduce_precision(scores, 8, 7) + bias,
                         top_k)[1]


def reference_logits(cfg, params, sequence, positions, reference,
                     forced=None):
    """The reference's next-token logits ``[P, V]`` at ``positions`` of
    ``sequence``, each expert layer's own chosen experts there ``[P, E]`` and
    each layer's latent rows there ``[P, 512 + 64]``.  ``forced = (rows,
    [sets [F, E] per expert layer])``: the experts those rows are computed
    over (the reference's ``forced``).  The sequence is padded to the
    configuration's ``max_seq_len``, the positions to whole chunks and the
    forced rows to the most a replay has, each by repeating its last: one
    compiled program for most lengths."""
    import jax
    import jax.numpy as jnp

    block = 128 if cfg["max_seq_len"] % 128 == 0 else cfg["page"]
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
            [np.asarray(r)[:n] for r in rows])


_REFERENCE_FN = {}


def gap(logits, token):
    """How far ``token`` sits below the top of ``logits``, in their standard
    deviations (0 where it is the top)."""
    return float((logits.max() - logits[int(token)]) / logits.std())


# ONE SCHEDULE, RUN TWICE over a checked sequence: through the engine's OWN
# compiled step programs into the engine's OWN cache after the drain (the
# executables of the window: ``DecodeScheduler.run_step``, every slot, the
# cell's pool: :func:`served_state_errors`, which reads the ``latent`` leaf
# they leave), and through the step FUNCTIONS under a ``jax.jit`` that also
# returns their logits and the experts ``moe_topk`` chose
# (:func:`replay`), on a cache of the cell's size.  The same tokens, pages and
# tables both times: ``sequence[:n]`` in chunks of ``chunk`` (``n`` = ``split``
# floored to a page), token ``n`` through the narrowest chunk program, then
# ``N_DECODE`` tokens decoded in slot 0 while every other slot decodes random
# tokens on a page of its own.
N_DECODE = 4


def _schedule(cfg, cache, sequence, split, seed, chunk, decode):
    """Run the schedule above: ``chunk(width, tokens, start, valid, pages,
    table_row)`` and ``decode(tokens, positions, tables, kv_lens)`` are the
    two programs.  Returns ``(pages held, first, end, chunk results, decode
    results)``: rows ``first .. end - 1`` are the last whole-width chunk's,
    the narrow chunk's and the decoded ones."""
    import jax.numpy as jnp

    S, ps, C = cfg["slots"], cfg["page"], cfg["chunk"]
    narrow = min(b for b in list(cfg["buckets"]) + [C] if b <= C)
    n = (min(split, len(sequence) - 1 - N_DECODE) // ps) * ps
    assert n > 0, "a checked sequence is a page and %d tokens" % (N_DECODE + 1)
    end = n + 1 + N_DECODE
    pages = cache.alloc(cache.pages_for(end))
    rest = [cache.alloc(1)[0] for _ in range(S - 1)]
    tables = np.zeros((S, cache.max_pages_per_seq), np.int32)
    tables[0] = cache.table_row(pages)
    tables[1:, 0] = rest
    rng = np.random.RandomState(seed % (2 ** 32))

    def one(width, start, valid):
        tokens = np.zeros(width, np.int32)
        tokens[:valid] = sequence[start:start + valid]
        vec = np.zeros(width // ps, np.int32)
        m = max(0, min(width // ps, len(pages) - start // ps))
        vec[:m] = pages[start // ps:start // ps + m]
        return chunk(width, jnp.asarray(tokens), jnp.int32(start),
                     jnp.int32(valid), jnp.asarray(vec),
                     jnp.asarray(tables[0]))

    chunks = [one(C, start, min(C, n - start)) for start in range(0, n, C)]
    chunks.append(one(narrow, n, 1))
    steps = []
    for pos in range(n + 1, end):
        tokens = rng.randint(0, cfg["vocab_size"], S).astype(np.int32)
        tokens[0] = sequence[pos]
        positions = np.full(S, pos - n, np.int32)
        positions[0] = pos
        steps.append(decode(jnp.asarray(tokens), jnp.asarray(positions),
                            jnp.asarray(tables), jnp.asarray(positions + 1)))
    return pages + rest, max(0, ((n - 1) // C) * C), end, chunks, steps


def replay_fns(cfg):
    """The two step functions under a ``jax.jit`` of their own that also
    returns the routing: made once a run, so that every checked request
    replays through the same executables."""
    import jax

    from paddle_tpu.models import deepseek_v3 as M

    donate = () if jax.default_backend() == "cpu" else (1,)
    return (jax.jit(lambda p, c, *a: M.prefill_chunk(
                p, *a[:3], c, *a[3:], cfg=cfg, with_routing=True),
                donate_argnums=donate),
            jax.jit(lambda p, c, *a: M.decode_step(
                p, *a[:2], c, *a[2:], cfg=cfg, with_routing=True),
                donate_argnums=donate))


def replay(cfg, params, sequence, split, seed, fns):
    """The step functions' own LOGITS and ROUTING on the schedule above
    (``fns`` from :func:`replay_fns`, a fresh cache of the cell's size).
    Returns ``(logits [2 + N_DECODE, V] at positions n - 1 .. end - 1, sets,
    first, end)``: ``sets`` one ``[end - first, E]`` bool mask per expert
    layer, the experts ``moe_topk`` computed rows ``first .. end - 1`` over."""
    import jax.numpy as jnp

    from paddle_tpu import serving
    from paddle_tpu.models import deepseek_v3 as M

    cache = serving.PagedKVCache(
        0, cfg["num_pages"], cfg["page"], 0, 0, cfg["max_seq_len"],
        dtype=cfg["kv_dtype"], num_slots=cfg["slots"],
        **M.cache_layout(cfg))
    pools = [cache.pools]
    n_exp = cfg["n_routed_experts"]

    def chunk(width, tokens, start, valid, pages, row):
        logits, pools[0], routing = fns[0](
            params, pools[0], tokens, start, valid, pages, row, jnp.int32(0))
        return (np.asarray(logits, np.float64),
                [_chosen_mask(np.asarray(r)[:int(valid)], n_exp)
                 for r in routing])

    def decode(tokens, positions, tables, lens):
        logits, pools[0], _, routing = fns[1](
            params, pools[0], tokens, positions, tables, lens)
        return (np.asarray(logits[0], np.float64),
                [_chosen_mask(np.asarray(r)[:1], n_exp) for r in routing])

    _, first, end, chunks, steps = _schedule(
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


# THE LATENT CACHE HELD ON THE OBJECT THAT IS TIMED (the engine's own
# executables on the engine's own cache, the schedule above).
#   latent_rows: the FIRST layer's rows of the ``latent`` leaf for the whole
#     sequence, the ones chunks wrote and the ones decode steps wrote alike.
#     A first layer's row depends on its token and position alone
#     (``[RMSNorm(norm1(E[tok]) W_kva) | rotary k_pe]``), so the reference
#     gives it without the cache, in float32: max |row - reference| / max
#     |reference|.  Served (a bfloat16 leaf under a product of bfloat16
#     operands) 3.3e-3 to 4.6e-3; the same rows kept in 8 bits (float8 e4m3:
#     ``latent_rows_8bit``, read beside it in every run from the served leaf
#     rounded once more) 4.3e-2 to 4.9e-2.  The limit sits between them in
#     the logarithm: three times the one, a third of the other.
#   latent_padding: the largest magnitude on the lanes past ``[c | k_pe]``
#     (zero: the kernels score over them).
#   latent_rows_deep: the rows of every LATER layer at positions ``first ..
#     end - 1``.  A later layer's row is a function of the experts its token
#     took in the layers before, so the reference computes these rows over
#     the experts the step functions' replay reports (``forced``): the share
#     of (row, layer) pairs whose distance from the reference's row, in the
#     row's own norm, is past ``DEEP_ROW_TOL``.  It holds the ENGINE'S
#     executables to the routing the replay reports, in every layer: a
#     token that took another expert than reported is another row from the
#     next layer on.  A sound row lies 3.8e-3 (layer 1, median) to 1.05e-2
#     (layer 5, the largest of 517 rows, nine runs) from the reference's, bfloat16
#     rounding that grows a layer at a time; a row routed apart lies 0.1 or
#     more away and none lies between 0.02 and 0.1: ``DEEP_ROW_TOL`` is three
#     times the largest sound distance.  Served: 0.0 of 2585 pairs (the
#     median and the largest distance are read beside it).  The same share on
#     the chunk BEFORE, whose rows the reference routes by itself
#     (``latent_rows_deep_unforced``, judged by nothing): 5.2e-2 to 7.6e-2,
#     none in layer 1 and 14% in layer 5 — what routing apart on one (row,
#     layer) set in twenty-five reads.  The limit is a fifth of the least.
SERVED_STATE_TOL = {"latent_rows": 1.4e-2, "latent_padding": 0.0,
                    "latent_rows_deep": 1e-2}
DEEP_ROW_TOL = 0.03


def served_state_errors(cfg, scheduler, sequence, split, seed, params,
                        reference):
    """``SERVED_STATE_TOL``'s first-layer readings from ``scheduler``'s own
    programs and cache (stopped, every page free), and for
    :func:`deep_row_errors` the rows they left in every layer at positions
    ``lo .. end - 1``: ``(errs, (lo, rows [L, end - lo, 512 + 64]))``."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.models import deepseek_v3 as M

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
        got = np.asarray(leaf.astype(jnp.float32), np.float64)
        deep = np.asarray(cache.pools["latent"][:, pages[lo // cfg["page"]:]]
                          .reshape(d["L"], -1, d["W"])[:, :end - lo]
                          .astype(jnp.float32), np.float64)
        want = np.asarray(jax.jit(lambda p, t: first_layer_rows(
            cfg, p, t, reference))(params, jnp.asarray(sequence[:end])),
            np.float64)
        width = d["R"] + d["dr"]
        scale = np.max(np.abs(want))
        errs["latent_rows"] = (
            float(np.max(np.abs(got[:, :width] - want)) / scale)
            if np.all(np.isfinite(got)) else float("inf"))
        errs["latent_padding"] = float(max(
            np.max(np.abs(got[:, width:])), np.max(np.abs(deep[..., width:])))
        ) if got.shape[-1] > width else 0.0
        eight = np.asarray(leaf.astype(jnp.float8_e4m3fn).astype(
            jnp.float32), np.float64)
        errs["latent_rows_8bit"] = float(
            np.max(np.abs(eight[:, :width] - want)) / scale)
    finally:
        cache.free(held)
    return errs, (lo, deep[..., :width])


def deep_row_errors(cfg, first, served, reference_rows):
    """``latent_rows_deep`` (rows ``first ..``, which the reference computed
    over the replay's experts; their median and largest distance beside it)
    and ``latent_rows_deep_unforced`` (the rows before them) from ``served = (lo, rows [L, n, W])`` and the reference's
    rows at the same positions ``[L, n, W]`` (rotary pairs interleaved)."""
    lo, got = served
    want = np.asarray(reference_rows, np.float64)
    R = cfg["kv_lora_rank"]
    want = np.concatenate([want[..., :R], want[..., R::2],
                           want[..., R + 1::2]], axis=-1)[1:]
    if not np.all(np.isfinite(got)):
        return {"latent_rows_deep": float("inf")}
    far = (np.linalg.norm(got[1:] - want, axis=-1)
           / np.linalg.norm(want, axis=-1))
    off = far > DEEP_ROW_TOL
    errs = {"latent_rows_deep": float(off[:, first - lo:].mean()),
            "latent_rows_deep_median": float(np.median(far[:, first - lo:])),
            "latent_rows_deep_max": float(far[:, first - lo:].max())}
    if first > lo:
        errs["latent_rows_deep_unforced"] = float(off[:, :first - lo].mean())
    return errs


def first_layer_rows(cfg, params, tokens, reference):
    """The rows ``[c | k_pe]`` the first layer caches for ``tokens`` at
    positions 0.., from the reference's own pieces in float32, the rotary
    part de-interleaved (evens, then odds) as the cache keeps it."""
    import jax
    import jax.numpy as jnp

    with jax.default_matmul_precision("highest"):
        H = cfg["num_attention_heads"]
        a = H * (cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"])
        R, eps = cfg["kv_lora_rank"], cfg["rms_norm_eps"]
        x = params["embed"][tokens].astype(jnp.float32)
        kva = reference.rms_norm(x, params["ln1"][0], eps) @ params[
            "layers"][0]["w_in"][:, a:].astype(jnp.float32)
        k_pe = reference.rope_interleaved(
            kva[:, R:], jnp.arange(tokens.shape[0], dtype=jnp.int32),
            float(cfg["rope_theta"]))
        return jnp.concatenate([
            reference.rms_norm(kva[:, :R], params["kvn"][0], eps),
            k_pe[:, 0::2], k_pe[:, 1::2]], axis=1)


# -- what a perfect decode step must move -------------------------------------

def _item(cfg):
    return 2 if cfg["weights_dtype"] == "bfloat16" else 4


def expert_params(cfg):
    """Parameters of ONE routed expert (gate, up, down)."""
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def weight_bytes(cfg):
    """Bytes of weights EVERY decode step reads whatever it routes: each
    layer's attention matrices, the dense blocks, the shared experts, the
    routers (float32) and the head; of the embedding only the rows looked
    up.  The routed experts are :func:`expert_bytes`."""
    from paddle_tpu.models import deepseek_v3 as M

    d = M._dims(cfg)
    n_moe = d["L"] - d["n_dense"]
    attn = (d["D"] * (d["H"] * (d["dn"] + d["dr"]) + d["R"] + d["dr"])
            + d["H"] * (d["dn"] + d["dv"]) * d["R"] + d["H"] * d["dv"] * d["D"])
    n = (d["L"] * attn + d["n_dense"] * 3 * d["D"] * d["F"]
         + n_moe * 3 * d["D"] * d["n_shared"] * d["Fm"]
         + d["D"] * d["V"] + cfg["slots"] * d["D"])
    return _item(cfg) * n + 4 * n_moe * (d["D"] + 1) * d["E"]


def expert_bytes(cfg, experts_touched):
    """Bytes of routed-expert weights a step reads: the experts that took a
    pair, summed over the expert layers (``serving.decode.moe.experts_touched``
    a step)."""
    return _item(cfg) * expert_params(cfg) * experts_touched


def latent_bytes(cfg, tokens_read):
    """Bytes of latent rows a step's attention reads: ``[c | k_pe]`` of every
    visible token of every slot in every layer
    (``serving.decode.latent.tokens_read`` a step).  The row as the model
    defines it (512 + 64), not the lane tiles the pool pads it to."""
    kv = 2 if cfg["kv_dtype"] == "bfloat16" else 4
    return kv * (cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"]) * tokens_read


def mla_flops(cfg, tokens_read):
    """Operations of the absorbed attention in one step: every head's score
    over a row's 576 values and ``P c`` over its 512."""
    return 2 * cfg["num_attention_heads"] * (
        2 * cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"]) * tokens_read


def moe_flops(cfg, pairs):
    """Operations of the routed experts in one step: a pair is one token
    through one expert's three matrices."""
    return 2 * expert_params(cfg) * pairs
