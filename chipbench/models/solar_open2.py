"""One holder's share of a Solar Open 2 model behind ``serving.InferenceEngine``
-> ``DecodeScheduler`` (``paddle_tpu/models/solar_open2.py``): the builders,
the checks against the plain reference at the configuration's own shapes, and
the bytes a perfect decode step must move.  Every size comes from the
configuration's file (the family's own key names)."""
from __future__ import annotations

import numpy as np

# THE LIMITS OF ``correct``, each with what it holds and its two readings (my
# chip runs, PR 40: eleven runs, eleven seeds; PERF.md section 6).  A limit
# lies between what the served program reads over its seeds and what the
# CONTROL it names reads: a lower precision than the configuration states, or
# a wrong mechanism.  A control named ``NOT_JUDGED`` is read in every run
# beside the sound one; a served program that WAS the control reads it in the
# judged entry (``tests/chipbench_tests/test_solar2_cell.py`` serves each).
#
# Each mechanism stand-alone, max |a - b| / max |b| against the plain reference
# (float32, highest precision) at the configuration's own shapes:
#   kda_decode: ``kda_state_decode`` as the step program calls it, on a random
#     float32 state a slot and head, some slots dead, against the reference's
#     ``kda_step`` from the SAME float32 q, k, v, g, beta: read-out and new
#     state.  Both sides float32 elementwise: served 0.0 in every run (4.8e-7
#     kernel-only).  CONTROL a bfloat16 state (``kda_decode_bf16_state``: the
#     new state rounded once): 2.7e-3 to 2.9e-3.  A dead slot that moved reads inf.
#   kda_prefill: ``kda_chunk`` (blocks of 64, the WY solve, decay differences)
#     over a ragged 512-token chunk from a random state against ``kda_step``
#     token by token.  Served 7.3e-6 to 1.0e-5.  CONTROLS, read once at these
#     shapes on the chip (my chip run, PR 40; PERF.md section 6): a
#     block that forgets the state of the block before it 0.28, ``beta``
#     halved 0.51, the decay of the row before 0.61; the new state rounded to
#     bfloat16 3.0e-3.
#   kda_layer_decode / kda_layer_prefill: the served delta-rule LAYER
#     (projections from bfloat16 weights, convolution from the slot's last
#     inputs, gates, kernel, read-out norm, W_o) against the reference's, the
#     mixer's own term (without the residual) and both leaves it leaves, from
#     the same rows, state and convolution inputs.  Served 3.1e-3 to 4.0e-3
#     (bfloat16 operands).  CONTROLS, each the reference's ``variant`` (a
#     served program that is the variant reads the same distance): ``beta1``
#     (no factor 2) 0.093 to 0.104, ``head_decay`` (one decay a head) 0.24 to
#     0.26, ``taps3`` (3 taps) 0.50 to 0.58, ``no_gate`` 0.53 to 0.60.  The limit is the geometric middle of
#     the sound reading and the least control.
#   gqa_layer_decode / gqa_layer_prefill: the served softmax LAYER (page walk
#     over bfloat16 pools, gate, W_o) against the reference's masked softmax
#     over the rows the pool holds.  Served 2.3e-3 to 3.3e-3.  CONTROLS
#     ``rotary`` (rotate-half rotary on q and k) 0.57 to 0.99 and ``no_gate``
#     0.51 to 0.59.
#   moe_decode / moe_prefill: ``moe_topk(experts_held=(0, 40))`` under the
#     router of 320 with the shared expert against the reference's loop over
#     the 40 held experts, the served weights of layer 0.  Served 1.4e-3 to
#     2.0e-3.  CONTROLS, read once at these shapes (as above): ONE held pair
#     dropped 0.071 to 0.124 over the first 64 held pairs of a step's and of a
#     chunk's rows, the last held expert dropped 0.097 / 0.114, the shared
#     expert counted twice or left out 0.99 to 1.0.
#   routing_mismatch: the share of (row, expert) entries on which the served
#     router's chosen sets differ from the reference's, from the SAME float32
#     rows.  Served 0.0.  CONTROL a bfloat16 router (``routing_mismatch_bf16``):
#     7.8e-3 to 1.2e-2.
MECHANISM_RTOL = {"kda_decode": 1e-4, "kda_prefill": 2e-3,
                  "kda_layer_decode": 1.8e-2, "kda_layer_prefill": 1.8e-2,
                  "gqa_layer_decode": 2e-2, "gqa_layer_prefill": 2e-2,
                  "moe_decode": 1e-2, "moe_prefill": 1e-2,
                  "routing_mismatch": 1.5e-3}
KDA_VARIANTS = ("beta1", "head_decay", "taps3", "no_gate")
GQA_VARIANTS = ("rotary", "no_gate")
ROUTED_ROWS = 1024      # rows the router alone is read on
NOT_JUDGED = tuple(
    ["kda_decode_bf16_state", "routing_mismatch_bf16", "kv_rows_rotary",
     "kda_state_bf16", "kda_state_median"]
    + ["kda_layer_decode_" + v for v in KDA_VARIANTS]
    + ["gqa_layer_decode_" + v for v in GQA_VARIANTS])
# TOP-8 IS A DISCRETE CHOICE (PR 33's finding holds): the logits are compared
# OVER THE SAME EXPERTS (the reference's ``forced``), the choice itself apart,
# and the served tokens are held to the reference in their SHARE.
# ``models/deepseek_v3.py`` has the reasoning of each limit; read here over
# eleven runs: logits 0.027 to 0.042 standard deviations from the reference's
# (``LOGIT_TOL``), 1.0 of 128 served tokens within ``TIE_TOL`` of its top
# (largest gap 0.041), 0.9925 to 0.9941 of its experts chosen too; another
# model's logits read 0.0 of the tokens.
LOGIT_TOL = 0.1
TIE_TOL = 0.15
CHECKED_TOKENS = 128
TOKENS_AGREE = 0.7
ROUTING_AGREE = 0.95


def make_params(cfg, seed):
    from paddle_tpu import observability as obs
    from paddle_tpu.models import solar_open2 as M

    with obs.span("serving.model_load", model="solar-open2-weights"):
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
    from paddle_tpu.models import solar_open2 as M

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


def _kda_inputs(d, key, rows):
    """Random float32 q, k (unit a head), v, g (<= 0), beta (0 .. 2) for
    ``rows`` tokens: what a delta-rule layer hands its recurrence."""
    import jax
    import jax.numpy as jnp

    ks = jax.random.split(key, 5)
    shape = (rows, d["Hl"], d["Dl"])
    q, k, v = (jax.random.normal(kk, shape, jnp.float32) for kk in ks[:3])
    unit = lambda x: x / jnp.linalg.norm(x, axis=-1, keepdims=True)  # noqa: E731
    g = -jnp.exp(jax.random.uniform(ks[3], shape, jnp.float32, -7.0, 0.5))
    beta = 2 * jax.random.uniform(ks[4], shape[:2], jnp.float32)
    return unit(q), unit(k), v, g, beta


def mechanism_errors(cfg, params, seed, reference):
    """The mechanisms as the step programs call them (the engine the program
    picks here) against the plain reference at the configuration's head
    counts, widths, page size, slots and chunk, on seeded random inputs and
    the served weights of the first layer of each kind."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.models import solar_open2 as M
    from paddle_tpu.parallel import kda, moe

    d = M._dims(cfg)
    S, C, ps = cfg["slots"], cfg["chunk"], cfg["page"]
    ks = jax.random.split(jax.random.PRNGKey((seed + 5) % (2 ** 31)), 16)
    act = params["embed"].dtype
    f32 = jnp.float32
    errs = {}
    state_shape = (S, d["Hl"], d["Dl"], d["Dl"])
    lk, lg = d["kinds"].index("kda"), d["kinds"].index("gqa")
    live = np.ones(S, bool)
    live[[S // 3, S - 1]] = False
    if S > 4:
        live[0] = False
    live_j = jnp.asarray(live)

    # -- the kernel: one token a slot from a random state
    state = jax.random.normal(ks[0], (1,) + state_shape, f32)
    q, k, v, g, beta = _kda_inputs(d, ks[1], S)
    want_o, want_s = jax.jit(jax.vmap(reference.kda_step))(
        state[0], q, k, v, g, beta)
    got_o, got_s = jax.jit(lambda *a: kda.kda_state_decode(
        *a, layer=0))(state, q, k, v, g, beta, live_j)
    errs["kda_decode"] = max(_rel(got_o[live_j], want_o[live_j]),
                             _rel(got_s[0][live_j], want_s[live_j]))
    errs["kda_decode_bf16_state"] = _rel(
        got_s[0][live_j].astype(jnp.bfloat16).astype(f32), want_s[live_j])
    if not bool((got_s[0][~live_j] == state[0][~live_j]).all()) or bool(
            got_o[~live_j].any()):
        errs["kda_decode_dead_slot_moved"] = float("inf")
    del got_s, want_s

    # -- the chunk-wise form: a ragged chunk from a random state
    valid = C - max(1, C // 14)
    q, k, v, g, beta = _kda_inputs(d, ks[2], C)
    s0 = state[0, 0]

    def by_token(s0, q, k, v, g, beta):
        real = jnp.arange(C) < valid
        g = jnp.where(real[:, None, None], g, 0.0)
        beta = jnp.where(real[:, None], beta, 0.0)

        def token(S, xs):
            o, S = reference.kda_step(S, *xs)
            return S, o

        return jax.lax.scan(token, s0, (q, k, v, g, beta))

    want_s, want_o = jax.jit(by_token)(s0, q, k, v, g, beta)
    got_o, got_s = jax.jit(kda.kda_chunk)(q, k, v, g, beta, s0,
                                          jnp.int32(valid))
    errs["kda_prefill"] = max(_rel(got_o[:valid], want_o[:valid]),
                              _rel(got_s, want_s))

    # -- the delta-rule layer, decode and chunk, and its controls
    tail = jax.random.normal(ks[3], (1, S, d["K"] - 1, 3 * d["N"]), f32)
    conv_dt = jnp.dtype(cfg["conv_state_dtype"])
    tail = tail.astype(conv_dt)
    x = jax.random.normal(ks[4], (S, d["D"]), f32).astype(act)

    def served_decode(p, x, state, tail, live):
        h, cache = M._kda_decode_layer(
            d, p, p["layers"][lk], lk, x, {"kda": state, "conv": tail}, live)
        return h.astype(f32) - x.astype(f32), cache

    def plain_decode(p, x, state, tail, variant):
        def one(row):
            y, s1, t1 = reference.kda_layer(
                p, cfg, lk, row[0][None], row[1], row[2],
                jnp.ones((1,), bool), variant)
            return y[0] - row[0], s1, t1
        return jax.lax.map(one, (x.astype(f32), state[0],
                                 tail[0].astype(f32)))

    got, cache = jax.jit(served_decode)(params, x, state, tail, live_j)
    plain = jax.jit(plain_decode, static_argnums=(4,))
    want, want_s, want_t = plain(params, x, state, tail, None)
    errs["kda_layer_decode"] = max(
        _rel(got[live_j], want[live_j]),
        _rel(cache["kda"][0][live_j], want_s[live_j]),
        _rel(cache["conv"][0][live_j].astype(f32), want_t[live_j]))
    for variant in KDA_VARIANTS:
        errs["kda_layer_decode_" + variant] = _rel(
            got[live_j], plain(params, x, state, tail, variant)[0][live_j])
    del cache, want_s

    xc = jax.random.normal(ks[5], (C, d["D"]), f32).astype(act)
    slot = S // 2

    def served_chunk(p, x, state, tail):
        h, cache = M._kda_chunk_layer(
            d, p, p["layers"][lk], lk, x, {"kda": state, "conv": tail},
            jnp.int32(slot), jnp.asarray(False), jnp.int32(valid))
        return (h.astype(f32) - x.astype(f32), cache["kda"][0, slot],
                cache["conv"][0, slot].astype(f32))

    def plain_chunk(p, x, state, tail):
        y, s1, t1 = reference.kda_layer(
            p, cfg, lk, x.astype(f32), state[0, slot],
            tail[0, slot].astype(f32), jnp.arange(C) < valid)
        return y - x.astype(f32), s1, t1

    got = jax.jit(served_chunk)(params, xc, state, tail)
    want = jax.jit(plain_chunk)(params, xc, state, tail)
    errs["kda_layer_prefill"] = max(
        _rel(got[0][:valid], want[0][:valid]), _rel(got[1], want[1]),
        _rel(got[2], want[2]))
    del state, tail, got, want

    # -- the softmax layer over bfloat16 pools: decode and chunk
    Hkv, Dh = d["Hkv"], d["Dh"]
    T = min(9 * C + 3 * ps + 5, cfg["max_seq_len"] - C)
    npg = -(-(T + C) // ps)
    kv_dt = jnp.dtype(cfg["kv_dtype"])
    perm = 1 + jax.random.permutation(ks[6], npg).astype(jnp.int32)

    def pool(key):
        rows = jax.random.normal(key, (npg, ps, Hkv * Dh), f32).astype(kv_dt)
        return jnp.zeros((1, npg + 1, ps, Hkv * Dh), kv_dt).at[0, perm].set(
            rows)

    cache = {"k": pool(ks[7]), "v": pool(ks[8])}
    # every slot its own length: the rows the step writes do not collide
    lens = np.linspace(ps + 1, T, S).astype(np.int32)
    lens[~live] = 0
    positions = np.maximum(lens - 1, 0)
    tables = jnp.broadcast_to(perm[None, :], (S, npg))
    pages = tables[jnp.arange(S), positions // ps]

    def served_gqa(p, x, cache):
        h, cache = M._gqa_decode_layer(
            d, p, p["layers"][lg], lg, x, dict(cache), tables,
            jnp.asarray(lens), jnp.where(live_j, pages, 0),
            jnp.asarray(positions % ps))
        return h.astype(f32) - x.astype(f32), cache

    def all_rows(cache, name):
        return cache[name][0, perm].reshape(-1, Hkv, Dh).astype(f32)

    def plain_gqa(p, x, cache, rows_at, variant):
        k, v = all_rows(cache, "k"), all_rows(cache, "v")
        if variant == "rotary":
            k = reference.rope(k, jnp.arange(k.shape[0]),
                               float(cfg["rope_theta"]))

        def one(row):
            return reference.gqa_layer(p, cfg, lg, row[0][None], row[1][None],
                                       k, v, variant)[0] - row[0]
        return jax.lax.map(one, (x.astype(f32), rows_at))

    got, after = jax.jit(served_gqa)(params, x, cache)
    plain = jax.jit(plain_gqa, static_argnums=(4,))
    at = jnp.asarray(positions)
    errs["gqa_layer_decode"] = _rel(
        got[live_j], plain(params, x, after, at, None)[live_j])
    for variant in GQA_VARIANTS:
        errs["gqa_layer_decode_" + variant] = _rel(
            got[live_j], plain(params, x, after, at, variant)[live_j])
    del after
    start = ((T - C) // ps) * ps

    def served_gqa_chunk(p, x, cache):
        h, cache = M._gqa_chunk_layer(
            d, p, p["layers"][lg], lg, x, dict(cache),
            perm[start // ps:start // ps + C // ps], perm, jnp.int32(start),
            jnp.int32(valid))
        return h.astype(f32) - x.astype(f32), cache

    got, after = jax.jit(served_gqa_chunk)(params, xc, cache)
    want = plain(params, xc, after, start + jnp.arange(C), None)
    errs["gqa_layer_prefill"] = _rel(got[:valid], want[:valid])
    del cache, after

    # -- the share of the expert layer at a decode step's and a chunk's rows
    held = d["held"]

    def served_moe(p, u):
        return moe.moe_topk(
            u.astype(act), {"w": p["router_w"][0], "bias": p["router_b"][0]},
            {"w_gu": p["e_gu"], "w_down": p["e_down"]},
            {"w_gu": p["layers"][0]["s_gu"], "w_down": p["layers"][0]["s_down"]},
            top_k=d["k"], experts_held=held, scale=d["scale"],
            scoring="sigmoid", layer=0)[0]

    def loop(p, u):
        return reference.moe_layer(
            u, p["router_w"][0], p["router_b"][0], p["e_gu"][0],
            p["e_down"][0], (p["layers"][0]["s_gu"], p["layers"][0]["s_down"]),
            d["k"], held, d["scale"])[0]

    served_moe, loop = jax.jit(served_moe), jax.jit(loop)
    for name, n, key in (("moe_decode", S, ks[9]), ("moe_prefill", C, ks[10])):
        u = jax.random.normal(key, (n, d["D"]), f32)
        u = u.astype(act).astype(f32)                # the same rows both sides
        errs[name] = _rel(served_moe(params, u), loop(params, u))
    # the router alone, from the same float32 rows on both sides
    u = jax.random.normal(ks[11], (ROUTED_ROWS, d["D"]), f32)
    w, b = params["router_w"][0], params["router_b"][0]
    want = np.asarray(jax.jit(lambda u, w, b: reference.route(
        u, w, b, d["k"])[0])(u, w, b))
    for name, route in (
            ("routing_mismatch", lambda x, w, b: moe.route_topk(
                x, w, b, top_k=d["k"], scoring="sigmoid")[0]),
            ("routing_mismatch_bf16", lambda x, w, b: _route_bf16(
                x, w, b, d["k"]))):
        got = _chosen_mask(jax.jit(route)(u, w, b), d["E"])
        errs[name] = float((got != want).sum() / want.sum())
    return errs


def _route_bf16(x, w, bias, top_k):
    """The experts a router would choose whose logits come from bfloat16
    operands and are kept in bfloat16: the lower precision's reading."""
    import jax
    import jax.numpy as jnp

    logits = jnp.dot(x.astype(jnp.bfloat16), w.astype(jnp.bfloat16),
                     preferred_element_type=jnp.float32)
    return jax.lax.top_k(jax.nn.sigmoid(
        jax.lax.reduce_precision(logits, 8, 7)) + bias, top_k)[1]


class _Leaves:
    """A delta-rule layer's two leaves after the last forced row: not rows,
    so a slice of positions of it is itself."""

    def __init__(self, state, conv):
        self.state, self.conv = state, conv

    def __getitem__(self, _):
        return self


def reference_logits(cfg, params, sequence, positions, reference,
                     forced=None):
    """The reference's next-token logits ``[P, V]`` at ``positions`` of
    ``sequence``, each layer's own chosen experts there ``[P, E]`` and what
    each layer's cache would keep: a softmax layer's K and V rows there ``[P,
    2, Hkv * head_dim]``, a delta-rule layer's :class:`_Leaves` after the
    last forced row.  ``forced = (rows, [sets [F, E] per layer])``: the experts
    those rows are computed over.  The sequence is padded to the
    configuration's ``max_seq_len``, the positions to whole chunks and the
    forced rows to the most a replay has, each by repeating its last: one
    compiled program for most lengths."""
    import jax
    import jax.numpy as jnp

    block = 32 if cfg["max_seq_len"] % 32 == 0 else cfg["page"]
    seq = np.zeros(-(-cfg["max_seq_len"] // block) * block, np.int32)
    seq[:len(sequence)] = sequence
    n, C = len(positions), cfg["chunk"]
    positions = list(positions) + [positions[-1]] * (-n % C)
    upto = len(sequence)
    if forced is not None:
        rows, sets = forced
        upto = rows[-1] + 1
        pad = C + 1 + N_DECODE - len(rows)
        forced = (jnp.asarray(list(rows) + [rows[-1]] * pad, jnp.int32),
                  [jnp.asarray(np.concatenate([s] + [s[-1:]] * pad))
                   for s in sets])
    key = (id(reference), len(positions), forced is not None)
    fn = _REFERENCE_FN.get(key)
    if fn is None:
        fn = _REFERENCE_FN[key] = jax.jit(
            lambda p, s, q, f, upto: reference.forward(
                p, cfg, s, q, block=block, forced=f, upto=upto))
    logits, chosen, kept = fn(params, jnp.asarray(seq),
                              jnp.asarray(positions, jnp.int32), forced,
                              jnp.int32(upto))
    kinds = reference.kinds(cfg)
    return (np.asarray(logits[:n], np.float64),
            [np.asarray(c)[:n] for c in chosen],
            [np.stack([np.asarray(a)[:n], np.asarray(b)[:n]], axis=1)
             if kind == "gqa" else
             _Leaves(np.asarray(a, np.float64), np.asarray(b, np.float64))
             for kind, (a, b) in zip(kinds, kept)])


_REFERENCE_FN = {}


def gap(logits, token):
    """How far ``token`` sits below the top of ``logits``, in their standard
    deviations (0 where it is the top)."""
    return float((logits.max() - logits[int(token)]) / logits.std())


# ONE SCHEDULE, RUN TWICE over a checked sequence (as ``models/deepseek_v3.py``
# does): through the engine's OWN compiled step programs into the engine's OWN
# cache after the drain (:func:`served_state_errors`, which reads the K and V
# rows and BOTH slot-state leaves they leave), and through the step FUNCTIONS
# under a ``jax.jit`` that also returns their logits and the experts
# ``moe_topk`` chose (:func:`replay`), on a cache of the cell's size.  The same
# tokens, pages, tables and SLOT both times: ``sequence[:n]`` in chunks of
# ``chunk`` (``n`` = ``split`` floored to a page; the first chunk takes the
# slot's leaves as zero whatever the engine left there), token ``n`` through
# the narrowest chunk program, then ``N_DECODE`` tokens decoded in the slot
# while every other slot decodes random tokens on a page of its own.
N_DECODE = 4
CHECK_SLOT = 0


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
    tables[CHECK_SLOT] = cache.table_row(pages)
    others = [s for s in range(S) if s != CHECK_SLOT]
    tables[others, 0] = rest
    rng = np.random.RandomState(seed % (2 ** 32))

    def one(width, start, valid):
        tokens = np.zeros(width, np.int32)
        tokens[:valid] = sequence[start:start + valid]
        vec = np.zeros(width // ps, np.int32)
        m = max(0, min(width // ps, len(pages) - start // ps))
        vec[:m] = pages[start // ps:start // ps + m]
        return chunk(width, jnp.asarray(tokens), jnp.int32(start),
                     jnp.int32(valid), jnp.asarray(vec),
                     jnp.asarray(tables[CHECK_SLOT]))

    chunks = [one(C, start, min(C, n - start)) for start in range(0, n, C)]
    chunks.append(one(narrow, n, 1))
    steps = []
    for pos in range(n + 1, end):
        tokens = rng.randint(0, cfg["vocab_size"], S).astype(np.int32)
        tokens[CHECK_SLOT] = sequence[pos]
        positions = np.full(S, pos - n, np.int32)
        positions[CHECK_SLOT] = pos
        steps.append(decode(jnp.asarray(tokens), jnp.asarray(positions),
                            jnp.asarray(tables), jnp.asarray(positions + 1)))
    return pages + rest, max(0, ((n - 1) // C) * C), end, chunks, steps


def replay_fns(cfg):
    """The two step functions under a ``jax.jit`` of their own that also
    returns the routing: made once a run, so that every checked request
    replays through the same executables."""
    import jax

    from paddle_tpu.models import solar_open2 as M

    donate = () if jax.default_backend() == "cpu" else (1,)
    return (jax.jit(lambda p, c, *a: M.prefill_chunk(
                p, *a[:3], c, *a[3:], cfg=cfg, with_routing=True),
                donate_argnums=donate),
            jax.jit(lambda p, c, *a: M.decode_step(
                p, *a[:2], c, *a[2:], cfg=cfg, with_routing=True),
                donate_argnums=donate))


def fresh_cache(cfg):
    """A cache of the cell's size and leaves, as the scheduler builds it."""
    from paddle_tpu import serving
    from paddle_tpu.models import solar_open2 as M

    lay = M.cache_layout(cfg)
    return serving.PagedKVCache(
        lay["num_layers"], cfg["num_pages"], cfg["page"], lay["num_heads"],
        lay["head_dim"], cfg["max_seq_len"], dtype=cfg["kv_dtype"],
        num_slots=cfg["slots"], slot_state=lay["slot_state"])


def replay(cfg, params, sequence, split, seed, fns):
    """The step functions' own LOGITS and ROUTING on the schedule above
    (``fns`` from :func:`replay_fns`, a fresh cache of the cell's size).
    Returns ``(logits [2 + N_DECODE, V] at positions n - 1 .. end - 1, sets,
    first, end)``: ``sets`` one ``[end - first, E]`` bool mask per layer over
    ALL the router's experts, those ``moe_topk`` chose for rows ``first .. end
    - 1``."""
    import jax.numpy as jnp

    cache = fresh_cache(cfg)
    pools = [cache.pools]
    n_exp = cfg["router_experts"]

    def chunk(width, tokens, start, valid, pages, row):
        logits, pools[0], routing = fns[0](
            params, pools[0], tokens, start, valid, pages, row,
            jnp.int32(CHECK_SLOT))
        return (np.asarray(logits, np.float64),
                [_chosen_mask(np.asarray(r)[:int(valid)], n_exp)
                 for r in routing])

    def decode(tokens, positions, tables, lens):
        logits, pools[0], _, routing = fns[1](
            params, pools[0], tokens, positions, tables, lens)
        return (np.asarray(logits[CHECK_SLOT], np.float64),
                [_chosen_mask(np.asarray(r)[CHECK_SLOT:CHECK_SLOT + 1], n_exp)
                 for r in routing])

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


# WHAT THE ENGINE'S OWN EXECUTABLES LEAVE IN THE ENGINE'S OWN CACHE (the object
# that was timed, the schedule above):
#   kv_rows: the softmax layer 0's K and V rows at every position.  A first
#     layer's row depends on its token alone (``norm1(E[tok]) W_k``, ``.. W_v``:
#     no rotation), so the reference gives it without the cache, in float32:
#     max |row - reference| / max |reference| over K and V.  Served 3.4e-3 to 3.8e-3 (a
#     bfloat16 row).  CONTROL ``kv_rows_rotary``: rotate-half rotary applied
#     to K reads 1.75 to 1.98.
#   kda_state: the delta-rule state of layers 1-3 in the checked slot after
#     the last decoded token, against the reference's token-by-token state
#     over the experts the replay reports for rows ``first ..`` (``forced``):
#     the largest over the layers of ||S - S_ref||_F / ||S_ref||_F, all heads
#     together.  It holds the chunk-wise form and the chunk-to-chunk and
#     chunk-to-step carry on the SERVED leaf.  CONTROLS, each a served
#     program through the cell at these widths (my chip run, PR 40; PERF.md
#     section 6): a chunk program that drops the carry (every chunk
#     from zero) reads 0.97 (``conv_state`` 0.50), 3 taps 0.88 (0.44).  (A
#     state left by the slot's last occupant has decayed away by the end of a
#     context of thousands: the reset of a reseated slot is held by
#     ``tests/unittests/test_solar_open2.py`` on short sequences.)  Served
#     1.08e-2 to 1.11e-2 (median layer 8.4e-3 to 8.6e-3): rows before
#     ``first`` are routed by each side for itself, and a recurrent state
#     remembers them.  ``kda_state_bf16`` is the same reading of the served
#     state rounded to bfloat16: 1.09e-2 to 1.12e-2, 1.2e-4 above the sound
#     reading in every run, so THIS reading cannot see a bfloat16 state (the
#     routing's own trace is a hundred times the rounding); the control of a
#     bfloat16 state is ``kda_decode``'s, where nothing else differs.
#   conv_state: the convolution's last 3 inputs of layers 1-3 in that slot,
#     max |a - b| / max |b|.  Served 6.4e-3 to 7.6e-3; under a dropped carry
#     the later layers' inputs are as wrong as the state of the layer before
#     them (0.50, above).  A wrong SOFTMAX layer (rotary, no gate) moves both
#     leaves to 0.036 and 0.023 only: its own limits name it.
SERVED_STATE_TOL = {"kv_rows": 1.4e-2, "kda_state": 6e-2, "conv_state": 6e-2}


def served_state_errors(cfg, scheduler, sequence, split, seed, params,
                        reference):
    """``SERVED_STATE_TOL``'s first-layer reading from ``scheduler``'s own
    programs and cache (stopped, every page free), and for
    :func:`deep_row_errors` the two slot-state leaves they left in the
    checked slot: ``(errs, (kda [L_kda, H, d, d], conv [L_kda, K - 1, 3N]))``
    float64."""
    import jax
    import jax.numpy as jnp

    cache = scheduler.cache
    zeros = (jnp.zeros((cfg["slots"],), jnp.uint32),
             jnp.zeros((cfg["slots"],), jnp.float32))

    def chunk(width, *args):
        scheduler.run_step(("chunk", width), *args, np.int32(CHECK_SLOT),
                           np.uint32(0), np.float32(0))

    def decode(*args):
        scheduler.run_step(("decode",), *args, *zeros)

    held, errs = [], {}
    try:
        held, _, end, _, _ = _schedule(
            cfg, cache, sequence, split, seed, chunk, decode)
        pages = jnp.asarray(held[:cache.pages_for(end)])
        got = [cache.pools[name][0, pages].reshape(
            len(pages) * cfg["page"], -1)[:end].astype(jnp.float32)
            for name in ("k", "v")]
        tokens = jnp.asarray(sequence[:end])
        rows = jax.jit(lambda p, t, variant=None: reference.gqa_rows(
            p, cfg, 0, p["embed"][t].astype(jnp.float32),
            jnp.arange(t.shape[0]), variant), static_argnums=(2,))
        want = rows(params, tokens)
        errs["kv_rows"] = max(_rel(g, w) for g, w in zip(got, want))
        errs["kv_rows_rotary"] = _rel(got[0], rows(params, tokens, "rotary")[0])
        leaves = tuple(np.asarray(
            cache.pools[name][:, CHECK_SLOT].astype(jnp.float32), np.float64)
            for name in ("kda", "conv"))
    finally:
        cache.free(held)
    return errs, leaves


def deep_row_errors(cfg, first, served, reference_rows):
    """``kda_state`` and ``conv_state`` from ``served = (kda, conv)`` (the
    checked slot's leaves) and the reference's kept values per layer (a
    delta-rule layer's :class:`_Leaves`)."""
    import jax.numpy as jnp

    kda, conv = served
    want = [r for r in reference_rows if isinstance(r, _Leaves)]
    if not (np.all(np.isfinite(kda)) and np.all(np.isfinite(conv))):
        return {"kda_state": float("inf"), "conv_state": float("inf")}

    def far(got, ref):
        return float(np.linalg.norm(got - ref) / max(np.linalg.norm(ref),
                                                     1e-30))

    rounded = np.asarray(jnp.asarray(kda, jnp.float32).astype(
        jnp.bfloat16).astype(jnp.float32), np.float64)
    dist = [far(g, w.state) for g, w in zip(kda, want)]
    return {"kda_state": max(dist), "kda_state_median": float(np.median(dist)),
            "kda_state_bf16": max(far(g, w.state)
                                  for g, w in zip(rounded, want)),
            "conv_state": max(_rel(g, w.conv) for g, w in zip(conv, want))}


# -- what a perfect decode step must move -------------------------------------

def _item(cfg):
    return 2 if cfg["weights_dtype"] == "bfloat16" else 4


def _lin(cfg):
    lin = cfg["linear_attn_config"]
    return lin["num_heads"], lin["head_dim"], lin["short_conv_kernel_size"]


def _kinds(cfg):
    n_gqa = len(cfg["gqa_layers"])
    return n_gqa, cfg["num_hidden_layers"] - n_gqa


def expert_params(cfg):
    """Parameters of ONE routed (or shared) expert (gate, up, down)."""
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def mixer_params(cfg):
    """``(softmax layer, delta-rule layer)`` mixer parameters (matrices)."""
    D = cfg["hidden_size"]
    n_q = cfg["num_attention_heads"] * cfg["head_dim"]
    n_kv = cfg["num_key_value_heads"] * cfg["head_dim"]
    H, d, _ = _lin(cfg)
    r = cfg["kda_gate_rank"]
    return (D * (2 * n_q + 2 * n_kv) + n_q * D,
            D * 3 * H * d + D * (2 * r + H) + 2 * r * H * d + H * d * D)


def weight_bytes(cfg):
    """Bytes of weights EVERY decode step reads whatever it routes: each
    layer's mixer, shared expert and router (float32, all its width), the
    head; of the embedding only the rows looked up.  The held experts are
    :func:`expert_bytes`."""
    D = cfg["hidden_size"]
    n_gqa, n_kda = _kinds(cfg)
    gqa, kda = mixer_params(cfg)
    L = cfg["num_hidden_layers"]
    n = (n_gqa * gqa + n_kda * kda
         + L * cfg["n_shared_experts"] * expert_params(cfg)
         + D * cfg["vocab_size"] + cfg["slots"] * D)
    return _item(cfg) * n + 4 * L * D * cfg["router_experts"]


def expert_bytes(cfg, experts_touched):
    """Bytes of expert weights a step reads: the HELD experts that took a
    pair, summed over the layers (``serving.decode.moe.experts_touched``)."""
    return _item(cfg) * expert_params(cfg) * experts_touched


def kv_bytes(cfg, full_tokens):
    """Bytes of K and V rows a step's softmax layers read: every cached
    position of every slot (``serving.decode.kv.full_tokens_read`` a step), a
    K and a V row of ``Hkv * head_dim`` values each."""
    kv = 2 if cfg["kv_dtype"] == "bfloat16" else 4
    return 2 * cfg["num_key_value_heads"] * cfg["head_dim"] * kv * full_tokens


def state_bytes(cfg, slot_updates):
    """Bytes of delta-rule state a step reads AND writes: ``[H, d, d]``
    float32 each way a (slot, layer) update
    (``serving.decode.kda.slot_updates`` a step)."""
    H, d, _ = _lin(cfg)
    return 2 * 4 * H * d * d * slot_updates


def conv_bytes(cfg, slot_updates):
    """Bytes of convolution state an update must move: the ``K - 1`` kept
    inputs read and the newest one written."""
    H, d, K = _lin(cfg)
    item = 2 if cfg["conv_state_dtype"] == "bfloat16" else 4
    return K * 3 * H * d * item * slot_updates


def step_bytes(cfg, counts):
    """Everything a perfect decode step must move, from the step's own
    counters (``solar_decode.step_counts``)."""
    return (weight_bytes(cfg) + expert_bytes(cfg, counts["experts_touched"])
            + kv_bytes(cfg, counts["full_tokens"])
            + state_bytes(cfg, counts["slot_updates"])
            + conv_bytes(cfg, counts["slot_updates"]))
