"""An SDAR-family model behind ``serving.InferenceEngine`` ->
``DecodeScheduler`` (``paddle_tpu/models/sdar.py``): the builders, and the
checks of a model that generates by DIFFUSION OVER BLOCKS against the plain
reference at the configuration's own shapes.  Every size comes from the
configuration's file (the family's own key names); what a perfect forward must
move is in ``chipbench/sdar_decode.py``."""
from __future__ import annotations

import numpy as np

# THE LIMITS OF ``correct``, each with what it holds and its two readings (my
# chip runs, PR 60; PERF.md section 6).
#
# The kernels stand-alone against the plain reference's attention under the
# block mask (float32, highest precision) over the same bfloat16 rows, at the
# configuration's head counts, page size, slots and chunk, max |a - b| / max
# |b|:
#   walk_decode: a slot's whole block a grid step (``q_tokens`` = B, 32 query
#     rows a KV head, no stagger), slots from one block to the whole pool, one
#     empty.  walk_chunk: a ragged chunk late in the sequence, two blocks a
#     grid step.  Both products run over exact bfloat16 parts, so nothing is
#     rounded that the reference does not round.
#   ``walk_decode_causal`` / ``walk_chunk_causal`` (read beside them in every
#     run, judged by nothing): the same kernel under the CAUSAL rule (``block``
#     = 1), the shortcut a block model must not take.
#   moe_decode: ``moe_topk(scoring="softmax")`` at a decode step's slots x B
#     rows against the reference's masked loop over all experts, the served
#     weights of layer 0 (bfloat16 operands).
PAGED_RTOL = {"walk_decode": 1e-3, "walk_chunk": 2e-3, "moe_decode": 8e-3}
NOT_JUDGED = ("walk_decode_causal", "walk_chunk_causal", "kv_rows_8bit",
              "kv_rows_deep_median", "kv_rows_deep_max", "kv_rows_stale")
# TOP-8 IS A DISCRETE CHOICE (PR 33's finding for top-6 holds): a forward's
# logits are compared OVER THE SAME EXPERTS (the reference's ``forced``) and
# the choice itself apart.  ``LOGIT_TOL``: max |replayed - reference| over a
# forward's [B, V] logits in the reference's standard deviations (6 layers of
# bfloat16 weights and K/V).  ``TIE_TOL``: how far below the reference's top a
# served id or an unmasked position's confidence may lie (logit standard
# deviations; a confidence is a softmax probability, so its logarithm moves as
# a logit does) before it counts as another answer.  Read over 20 runs of the
# final weights (my chip run, PR 60): logits 0.051-0.112 sigma, against 1.83-
# 2.56 where the same logits are read SHIFTED by one position and more under a
# causal block (``logits_shifted`` beside every check): the limit is nearly
# three times the largest sound reading and a sixth of the least wrong one.
# Confidence gaps 0-0.033; routed experts 0.953-0.990 of the reference's (a
# wrong router agrees on k / E = 0.06).
# ``IDS_AGREE``: THE IDS THE TIMED WINDOW SERVED (64 slots live, a step in
# flight, the blocks' state carried on the device), each under the reference's
# logits of the forward that wrote it on the served trajectory: the share of a
# request's checked ids (12: three blocks) within ``TIE_TOL`` of the top.  In
# their share, as ``serve_standing_moe.py`` holds served tokens: the timed
# engine's own top-8 choice is not the replay's wherever a near-tie turns on
# the K/V rows' rounding (its rows were written by decode forwards, the
# replay's by chunks), and the host never sees it.  Read over 7 runs, 14
# requests (my chip run, PR 60, seeds 2800000029-141): 13 requests 1.0 with
# every gap 0-0.050, one 11 of 12 = 0.917 (one id 0.207 below the top, the
# last position of its last block, behind 1696 decode-written rows); THE IDS
# ANOTHER SLOT WAS SERVED at the same place (a block's state landed in the
# wrong slot; ``ids_agree_other_slot``) 0.0 in all 14, the nearest 2.22 below
# the top.
LOGIT_TOL = 0.3
TIE_TOL = 0.15
ROUTING_AGREE = 0.9
IDS_AGREE = 0.7
# rows the engine's own executables (and the replay's) leave in the cache,
# against the reference's: ``kv_rows`` layer 0's (a function of the token and
# its position alone), max |row - reference| / max |reference|, with the same
# rows kept in 8 bits beside it (``kv_rows_8bit``: the lower precision's
# reading); ``kv_rows_deep`` the share of the later layers' (row, K | V)
# entries further than ``DEEP_ROW_TOL`` (in the row's norm) from the
# reference's over the same experts; ``kv_rows_stale`` what the rows a
# DENOISING forward left (the block still held mask ids) read in that measure:
# the shortcut of keeping them.  ``state_mismatch``: positions of a block's
# state on which the engine's own decode program and the replay differ.
# Read over 13 runs (my chip run, PR 60): ``kv_rows`` 0.0024-0.0042 against
# 0.032-0.053 in 8 bits; the later layers' rows at most 0.024 from the
# reference's (``DEEP_ROW_TOL`` is 2.5 times that) and none past it, against
# 0.63-0.82 of a denoising forward's rows.
SERVED_STATE_TOL = {"kv_rows": 1.4e-2, "kv_rows_deep": 5e-2,
                    "state_mismatch": 0.0}
DEEP_ROW_TOL = 0.06


def make_params(cfg, seed):
    from paddle_tpu import observability as obs
    from paddle_tpu.models import sdar as M

    with obs.span("serving.model_load", model="sdar-weights"):
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
    from paddle_tpu.models import sdar as M

    return serving.InferenceEngine(
        decode_model=M.build_decode_model(params, cfg),
        decode_config=decode_config(cfg, max_new_tokens))


def prompt_ids(cfg, prompt):
    """A prompt of the generator (ids ``1 .. vocab - 2``) over the vocabulary
    LESS THE MASK ID: ids from the mask id on move up by one."""
    prompt = np.asarray(prompt, np.int32)
    return prompt + (prompt >= cfg["mask_token_id"]).astype(np.int32)


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


def paged_kernel_errors(cfg, params, seed, reference):
    """The mechanisms as the step programs call them (the engine the program
    picks here) against the plain reference at the configuration's head
    counts, widths, page size, slots and chunk, on seeded random inputs and
    the served weights of layer 0."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.models import sdar as M
    from paddle_tpu.parallel import flash_attention as FA
    from paddle_tpu.parallel import moe

    d = M._dims(cfg)
    H, Hkv, Dh, B = d["H"], d["Hkv"], d["Dh"], d["B"]
    ps, C, S = cfg["page"], cfg["chunk"], cfg["slots"]
    T = min(9 * C + 3 * ps + B, cfg["max_seq_len"] - C) // B * B
    npg = -(-(T + C) // ps)
    ks = jax.random.split(jax.random.PRNGKey((seed + 5) % (2 ** 31)), 8)
    kv_dt = jnp.dtype(cfg["kv_dtype"])
    act = params["embed"].dtype
    k_rows = jax.random.normal(ks[0], (npg * ps, Hkv * Dh), jnp.float32
                               ).astype(kv_dt)
    v_rows = jax.random.normal(ks[1], (npg * ps, Hkv * Dh), jnp.float32
                               ).astype(kv_dt)
    perm = 1 + jax.random.permutation(ks[2], npg).astype(jnp.int32)

    def pool(rows):
        return jnp.zeros((1, npg + 1, ps, Hkv * Dh), kv_dt).at[0, perm].set(
            rows.reshape(npg, ps, -1))

    k_pool, v_pool = pool(k_rows), pool(v_rows)
    k_all = k_rows.astype(jnp.float32).reshape(-1, Hkv, Dh)
    v_all = v_rows.astype(jnp.float32).reshape(-1, Hkv, Dh)
    errs = {}
    plain = jax.jit(reference.attention, static_argnums=(4,))

    # decode: a block a slot, slots from one block to the whole pool, one empty
    lens = (np.linspace(B, T, S).astype(np.int32) // B) * B
    lens[S // 2] = 0
    live = lens > 0
    q = jax.random.normal(ks[3], (S, B, H, Dh), jnp.float32).astype(act)
    tables = jnp.broadcast_to(perm[None, :], (S, npg))
    at = (np.maximum(lens - B, 0)[:, None] + np.arange(B)[None, :]).reshape(-1)
    want = np.asarray(plain(q.reshape(S * B, H, Dh).astype(jnp.float32),
                            k_all, v_all, jnp.asarray(at), B)
                      ).reshape(S, B, H, Dh)
    for name, block in (("walk_decode", B), ("walk_decode_causal", 1)):
        got = np.asarray(jax.jit(
            lambda q, k, v, t, n, block=block: FA.paged_gqa_decode_attention(
                q, k, v, t, n, layer=0, sm_scale=d["sm_scale"], block=block))(
                    q, k_pool, v_pool, tables, jnp.asarray(lens)))
        errs[name] = _rel(got[live], want[live])
        if got[~live].any():
            errs[name + "_empty_slot_not_zero"] = float("inf")

    # prefill: one ragged chunk late in the sequence
    start = ((T - C) // ps) * ps
    valid = (C - max(B, C // 14)) // B * B
    qc = jax.random.normal(ks[4], (C, H, Dh), jnp.float32).astype(act)
    rows = start + jnp.arange(C, dtype=jnp.int32)
    want = np.asarray(plain(qc.astype(jnp.float32), k_all, v_all, rows, B)
                      )[:valid]
    for name, block in (("walk_chunk", B), ("walk_chunk_causal", 1)):
        got = jax.jit(
            lambda q, k, v, pages, block=block: FA.paged_gqa_prefill_attention(
                q, k, v, pages, jnp.int32(start), jnp.int32(valid), layer=0,
                sm_scale=d["sm_scale"], block=block))(qc, k_pool, v_pool, perm)
        errs[name] = _rel(np.asarray(got)[:valid], want)
    del k_pool, v_pool, k_all, v_all

    # the expert layer at a decode step's rows
    def served(p, u):
        return moe.moe_topk(
            u.astype(act), {"w": p["router_w"][0], "bias": None},
            {"w_gu": p["e_gu"], "w_down": p["e_down"]}, None, top_k=d["k"],
            experts_held=(0, d["E"]), scoring="softmax", layer=0)[0]

    def loop(p, u):
        return reference.moe_layer(u, p["router_w"][0], p["e_gu"],
                                   p["e_down"], d["k"], 0)[0]

    u = jax.random.normal(ks[5], (S * B, d["D"]), jnp.float32)
    u = u.astype(act).astype(jnp.float32)           # the same rows both sides
    errs["moe_decode"] = _rel(jax.jit(served)(params, u),
                              jax.jit(loop)(params, u))
    return errs


def gap(logits, token):
    """How far ``token`` sits below the top of ``logits``, in their standard
    deviations (0 where it is the top)."""
    return float((logits.max() - logits[int(token)]) / logits.std())


# ONE SCHEDULE, RUN TWICE over a checked request (its prompt and the ids it was
# served): through the step FUNCTIONS under a ``jax.jit`` that also returns
# their logits and the experts ``moe_topk`` chose (:func:`replay`), on a cache
# of a few sequences, and through the engine's OWN compiled step programs into
# the engine's OWN cache after the drain (:func:`served_state_errors`).  For
# each checked block ``b`` in ascending order: the sequence's rows before the
# block are prefilled under the block mask in chunks of ``chunk`` (from the
# last whole page the schedule has not passed: a chunk starts on a page), then
# the block is DENOISED AGAIN from its opening state (the prompt's leftover
# ids where it is the first decoded block, the mask id elsewhere) by the
# model's rule until it closes, and one more forward writes its K/V: what
# the scheduler's slot went through.  The replay FOLLOWS THE SERVED
# TRAJECTORY (:func:`follow_served`): where a forward unmasks a position, the
# id the timed window served there is seated, so every served id of a checked
# block is judged under the reference's logits of the forward that wrote it,
# and the next forward starts from the block the timed engine had.


def checked_blocks(cfg, prompt_len, served):
    """The first, a middle and the last WHOLE block the request was served
    (its last delivered ids may be part of a block still being denoised)."""
    B = cfg["block_length"]
    first = prompt_len // B
    last = (prompt_len + served) // B - 1
    if last < first:
        return []
    return sorted({first, (first + last) // 2, last})


def _opening(cfg, prompt, b):
    B = cfg["block_length"]
    ids = np.full((B,), cfg["mask_token_id"], np.int32)
    if b == len(prompt) // B:
        left = prompt[b * B:]
        ids[:len(left)] = left
    return ids


def _schedule(cfg, cache, prompt, served, blocks, chunk, forward):
    """``chunk(width, tokens, start, valid, pages written, table row)`` and
    ``forward(ids [B], start, forwards, table row, pages) -> (ids', unmasked,
    whole, extra)`` are the two programs.  Returns ``(release, pages, records)``:
    a record a forward, ``dict(block=, t=, ids=, kv=, unmasked=, extra=)``."""
    B, ps, C = cfg["block_length"], cfg["page"], cfg["chunk"]
    seq = np.concatenate([prompt, served]).astype(np.int32)
    end = (blocks[-1] + 1) * B
    pages = cache.alloc(cache.pages_for(end))
    row = cache.table_row(pages)
    widths = sorted(set(b for b in cfg["buckets"] if b < C) | {C})
    done, records = 0, []
    for b in blocks:
        while done < b * B:
            valid = min(C, b * B - done)
            w = next(x for x in widths if x >= valid)
            tokens = np.zeros(w, np.int32)
            tokens[:valid] = seq[done:done + valid]
            vec = np.zeros(w // ps, np.int32)
            m = max(0, min(w // ps, len(pages) - done // ps))
            vec[:m] = pages[done // ps:done // ps + m]
            chunk(w, tokens, done, valid, vec, row)
            done += valid
        done = (b * B // ps) * ps            # the next chunk starts on a page
        ids, t = _opening(cfg, prompt, b), 0
        while True:
            new, unmasked, whole, extra = forward(ids, b * B, t, row, pages)
            records.append(dict(block=b, t=t, ids=ids, kv=bool(whole),
                                unmasked=[int(i) for i in
                                          np.flatnonzero(unmasked)],
                                extra=extra))
            if whole:
                break
            ids, t = np.asarray(new, np.int32), t + 1
        records[-1]["final"] = ids
    return (lambda: cache.free(pages)), pages, records


def follow_served(cfg, ids, logits, unmasked, served):
    """The set a denoising forward of the TIMED engine unmasked, as far as
    the host can know it, and the block it left: ``ids [B]`` going in,
    ``logits [B, V]`` the replay's own, ``unmasked`` what the step program's
    rule takes on them, ``served [B]`` the block as it was served.  The set
    is the rule's, unless a served id there lies further than ``TIE_TOL``
    below the top of its logits while a masked position the rule left out,
    whose confidence lies within ``TIE_TOL`` of that one's (in logit standard
    deviations of its logarithm), has a served id that does not: the timed
    engine, whose confidences differ from the replay's by rounding, then took
    that one first (a near-tie between positions turned).  Against the
    reference's confidences the turn is judged again (``judge_forward``'s
    ``unmask_gap``).  A served id that is another answer with no such
    position beside it (a top-8 near-tie turned in the timed engine's router,
    whose choice the host never sees) is seated as served and counts against
    ``IDS_AGREE``.  Returns ``(ids', positions)``."""
    took = [int(i) for i in np.flatnonzero(unmasked)]
    masked = [i for i in range(len(ids)) if ids[i] == cfg["mask_token_id"]]
    far = {i: gap(logits[i], served[i]) > TIE_TOL for i in masked}
    # a greedy candidate's confidence, its logarithm negated: the row's
    # log-sum-exp less its top logit
    surplus = {i: np.log(np.exp(logits[i] - logits[i].max()).sum())
               for i in masked}
    spare = sorted((i for i in masked if i not in took and not far[i]),
                   key=lambda i: (surplus[i], i))
    for n, i in enumerate(took):
        if far[i] and spare and (surplus[spare[0]] - surplus[i]
                                 <= TIE_TOL * logits.std()):
            took[n] = spare.pop(0)
    new = np.array(ids, np.int32)
    new[took] = served[took]
    return new, sorted(took)


def replay_fns(cfg):
    """The two step functions under a ``jax.jit`` of their own that also
    returns the routing, and the step program's own unmasking rule: made once
    a run, so that every checked request replays through the same
    executables."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.models import sdar as M
    from paddle_tpu.serving import step_programs as SP

    donate = () if jax.default_backend() == "cpu" else (1,)
    blk = M.block(cfg)

    def unmask(ids, logits, forwards):
        keys = jax.random.split(jax.random.PRNGKey(0), ids.shape[0])
        return SP.unmask_block(
            ids, logits.astype(jnp.float32), keys, jnp.float32(0.0), forwards,
            mask_id=blk["mask_id"], steps=blk["steps"],
            threshold=blk["threshold"])

    return (jax.jit(lambda p, c, *a: M.prefill_chunk(
                p, *a[:3], c, *a[3:], cfg=cfg, with_routing=True),
                donate_argnums=donate),
            jax.jit(lambda p, c, *a: M.decode_step(
                p, *a[:2], c, *a[2:], cfg=cfg, with_routing=True),
                donate_argnums=donate),
            jax.jit(unmask))


def small_cache(cfg, tokens):
    """A cache of the cell's geometry with room for one sequence of
    ``tokens``."""
    from paddle_tpu import serving

    return serving.PagedKVCache(
        cfg["num_hidden_layers"], -(-tokens // cfg["page"]) + 2, cfg["page"],
        cfg["num_key_value_heads"], cfg["head_dim"], cfg["max_seq_len"],
        dtype=cfg["kv_dtype"], num_slots=cfg["slots"])


def _block_rows(cache, pages, cfg, b):
    """The K and V rows of block ``b`` in every layer ``[L, 2, B, width]``
    float64."""
    import jax.numpy as jnp

    B, ps = cfg["block_length"], cfg["page"]
    page, off = pages[b * B // ps], b * B % ps
    return np.stack([np.asarray(
        cache.pools[leaf][:, page, off:off + B].astype(jnp.float32),
        np.float64) for leaf in ("k", "v")], axis=1)


def replay(cfg, params, prompt, served, blocks, fns, follow=True):
    """The step functions' own LOGITS and ROUTING on the schedule above, on
    the served trajectory (``follow``; else free-running: the ids the step
    program's own rule writes, what ``served_state`` is held to).  Returns
    the forwards' records, each with ``logits [B, V]`` float64, ``sets`` (a
    ``[B, E]`` bool mask a layer: the experts ``moe_topk`` computed the
    block's rows over) and ``rows`` (the block's K and V rows as the forward
    left them ``[L, 2, B, width]``)."""
    import jax.numpy as jnp

    B, S = cfg["block_length"], cfg["slots"]
    seq = np.concatenate([prompt, served]).astype(np.int32)
    cache = small_cache(cfg, (blocks[-1] + 1) * B)
    pools = [cache.pools]
    n_exp = cfg["num_experts"]
    chunk_fn, decode_fn, unmask = fns

    def chunk(width, tokens, start, valid, written, row):
        _, pools[0], _ = chunk_fn(
            params, pools[0], jnp.asarray(tokens), jnp.int32(start),
            jnp.int32(valid), jnp.asarray(written), jnp.asarray(row),
            jnp.int32(0))

    def forward(ids, start, t, row, pages):
        tokens = np.zeros((S, B), np.int32)
        tokens[0] = ids
        starts, lens = np.zeros(S, np.int32), np.zeros(S, np.int32)
        starts[0], lens[0] = start, start + B
        tables = np.zeros((S, len(row)), np.int32)
        tables[0] = row
        logits, pools[0], _, routing = decode_fn(
            params, pools[0], jnp.asarray(tokens), jnp.asarray(starts),
            jnp.asarray(tables), jnp.asarray(lens))
        new, unmasked, whole = unmask(jnp.asarray(ids), logits[0],
                                      jnp.int32(t))
        cache.pools = pools[0]
        extra = dict(
            logits=np.asarray(logits[0], np.float64),
            sets=[_chosen_mask(np.asarray(r)[:B], n_exp) for r in routing],
            rows=_block_rows(cache, pages, cfg, start // B))
        new, unmasked = np.asarray(new), np.asarray(unmasked)
        if follow and not whole:
            new, took = follow_served(cfg, ids, extra["logits"], unmasked,
                                      seq[start:start + B])
            unmasked = np.isin(np.arange(B), took)
        return new, unmasked, bool(whole), extra

    release, _, records = _schedule(cfg, cache, prompt, served, blocks,
                                    chunk, forward)
    release()
    return records


def served_state(cfg, scheduler, prompt, served, block):
    """The stopped ``scheduler``'s OWN compiled programs on its OWN cache
    over the schedule above for one block: the state (``ids'``, flags) each
    decode dispatch returns, device-carried from dispatch to dispatch as the
    loop carries it, and the K and V rows of the block they leave.  Returns
    the forwards' records with ``rows`` in each."""
    from paddle_tpu.serving import step_programs as SP

    B, S = cfg["block_length"], cfg["slots"]
    cache = scheduler.cache
    previous = [None]

    def chunk(width, tokens, start, valid, written, row):
        scheduler.run_step(("chunk", width), tokens, np.int32(start),
                           np.int32(valid), written, row, np.int32(0),
                           np.uint32(0), np.float32(0))

    def forward(ids, start, t, row, pages):
        tokens = np.zeros((S, B), np.int32)
        tokens[0] = ids
        starts, ends = np.zeros(S, np.int32), np.zeros(S, np.int32)
        starts[0], ends[0] = start, cfg["max_seq_len"] // B * B
        tables = np.zeros((S, len(row)), np.int32)
        tables[0] = row
        forwards, from_previous = np.zeros(S, np.int32), np.zeros(S, np.int32)
        forwards[0] = t
        args = (tokens, starts, tables, ends, np.zeros(S, np.uint32),
                np.zeros(S, np.float32), forwards)
        if previous[0] is not None and t:
            # the block's state from the dispatch before, still on the device
            from_previous[0] = 1
            args += (previous[0], from_previous)
        previous[0] = scheduler.run_step(("decode",), *args)
        new, _, _, flags, _ = SP.block_state(np.asarray(previous[0]), S, B)
        whole = bool(flags[0] >> B)
        unmasked = [(int(flags[0]) >> i) & 1 for i in range(B)]
        extra = dict(rows=_block_rows(cache, pages, cfg, start // B))
        return (ids if whole else new[0]), np.asarray(unmasked), whole, extra

    release, _, records = _schedule(cfg, cache, prompt, served, [block],
                                    chunk, forward)
    release()
    return records


_REFERENCE_FN = {}
ROWS = 128              # query rows a block of the reference's attention


def reference_forward(cfg, params, context, ids, reference, sets=None):
    """The reference's forward over ``context`` (whole blocks) followed by
    one block ``ids``: the block's logits ``[B, V]`` float64, each layer's own
    chosen experts there ``[B, E]`` and its K and V rows there ``[L, 2, B,
    width]``.  ``sets``: the experts the block's rows are computed over (the
    reference's ``forced``).  The sequence is padded with the mask id (whole
    blocks behind the block: invisible to it) to a quarter of ``max_seq_len``
    or a multiple of it: a few compiled programs for every length."""
    import jax
    import jax.numpy as jnp

    B = cfg["block_length"]
    n = len(context)
    unit = -(-cfg["max_seq_len"] // (4 * ROWS)) * ROWS
    T = -(-(n + B) // unit) * unit
    seq = np.full(T, cfg["mask_token_id"], np.int32)
    seq[:n], seq[n:n + B] = context, ids
    at = jnp.arange(n, n + B, dtype=jnp.int32)
    forced = None if sets is None else (at, [jnp.asarray(s) for s in sets])
    key = (id(reference), T, sets is not None)
    fn = _REFERENCE_FN.get(key)
    if fn is None:
        fn = _REFERENCE_FN[key] = jax.jit(
            lambda p, s, q, f: reference.forward(p, cfg, s, q, rows=ROWS,
                                                 forced=f))
    logits, chosen, rows = fn(params, jnp.asarray(seq), at, forced)
    return (np.asarray(logits, np.float64), [np.asarray(c) for c in chosen],
            np.stack([np.stack([np.asarray(k, np.float64),
                                np.asarray(v, np.float64)])
                      for k, v in rows]))


def routing_agreement(served, reference_chosen):
    """Mean share of the reference's chosen experts that the served router
    chose too, over rows (``[rows, E]`` bool each), and the share of rows
    whose sets are equal."""
    both = (served & reference_chosen).sum(axis=1)
    want = np.maximum(reference_chosen.sum(axis=1), 1)
    return float((both / want).mean()), float(
        (served == reference_chosen).all(axis=1).mean())


def _far(got, want):
    """Each row's distance from the reference's, in the row's own norm."""
    return (np.linalg.norm(got - want, axis=-1)
            / np.maximum(np.linalg.norm(want, axis=-1), 1e-30))


def judge_forward(cfg, record, ref_logits, reference):
    """One replayed forward against the reference's logits over the same ids
    and experts: ``dict(logit_err=, unmask_gap=)``.  ``logit_err``:
    max |served - reference| in the reference's standard deviations, over the
    block's ``[B, V]``.  ``unmask_gap``: 0 where the forward unmasked the set
    the reference's logits give; else how far the best confidence the served
    set leaves out lies above the least it took, in the reference's own
    confidences' logarithm over a logit's standard deviation (a near-tie
    turned)."""
    std = float(ref_logits.std())
    out = {"logit_err": float(np.max(np.abs(
        record["extra"]["logits"] - ref_logits)) / std)}
    if record["kv"]:
        return out
    _, U, cand, conf = reference.unmask(record["ids"], ref_logits,
                                        record["t"], cfg)
    got = record["unmasked"]
    out["unmask_gap"] = 0.0
    if sorted(got) != sorted(U):
        took = [i for i in got if i not in U]
        left = [i for i in U if i not in got]
        if not took or not left or len(got) != len(U):
            out["unmask_gap"] = float("inf")
        else:
            out["unmask_gap"] = float(
                (np.log(max(conf[i] for i in left))
                 - np.log(min(conf[i] for i in took))) / std)
    return out


def judge_ids(record, after, ref_logits):
    """The ``id_gap`` of each position a denoising forward unmasked, ``after``
    the block as it left it (on the served trajectory: the ids the TIMED
    engine served there): how far below the reference's top the id it wrote
    lies, in logit standard deviations."""
    return [gap(ref_logits[i], after[i]) for i in record["unmasked"]]


def row_errors(cfg, records, ref_rows):
    """``kv_rows`` / ``kv_rows_deep`` of the K/V-writing forwards' rows (and
    ``kv_rows_stale``: the denoising forwards' in the deep measure) from the
    records' ``rows`` against the reference's of the WHOLE block ``{block:
    [L, 2, B, width]}``."""
    import jax.numpy as jnp

    errs = {"kv_rows": 0.0, "kv_rows_8bit": 0.0}
    deep, stale = [], []
    for r in records:
        want = ref_rows[r["block"]]
        got = r["extra"]["rows"]
        if not np.all(np.isfinite(got)):
            return {"kv_rows": float("inf"), "kv_rows_deep": float("inf")}
        if r["kv"]:
            errs["kv_rows"] = max(errs["kv_rows"], _rel(got[0], want[0]))
            low = np.asarray(jnp.asarray(got[0], jnp.float32).astype(
                jnp.float8_e4m3fn).astype(jnp.float32))
            errs["kv_rows_8bit"] = max(errs["kv_rows_8bit"],
                                       _rel(low, want[0]))
            deep.append(_far(got[1:], want[1:]).reshape(-1))
        else:
            stale.append(_far(got[1:], want[1:]).reshape(-1))
    if deep:
        deep = np.concatenate(deep)
        errs.update(kv_rows_deep=float((deep > DEEP_ROW_TOL).mean()),
                    kv_rows_deep_median=float(np.median(deep)),
                    kv_rows_deep_max=float(deep.max()))
    if stale:
        errs["kv_rows_stale"] = float(
            (np.concatenate(stale) > DEEP_ROW_TOL).mean())
    return errs
