"""``correct`` for a served model whose feed-forward blocks are routed experts
and whose cache the engine's own step programs can be run on again
(``DecodeScheduler.run_step``): what a serving driver of such a family decides
after its window, in ONE place.  ``drivers/serve_open_moe.py`` calls it;
``drivers/serve_standing_moe.py`` holds the same steps inline from before this
file and moves onto it in a ``benchmark`` PR (a PR of another kind edits no
benchmark file that is there).

Two calls, because the engine's pools and the checks' own do not fit the chip
together:

* :func:`served_state`, while the stopped engine lives: its OWN executables
  prefill a served request's sequence again into its OWN cache and decode on,
  and the rows they leave are read (``model.served_state_errors``);
* :func:`check`, once the engine is collected: the mechanisms stand-alone
  (``model.mechanism_errors``), and for each checked request the served
  tokens, the logits and the routed sets of the step functions on the same
  schedule (``model.replay``) against the plain reference, which computes the
  replayed rows over the experts the step functions took (top-k is a discrete
  choice); the rows the engine's executables left in the later layers are
  held to that reference too (``model.deep_row_errors``).

Every limit is the model builder's (``models/<config.model>.py``), with its two
readings there."""
from __future__ import annotations

import numpy as np


def split(prompt, out):
    """A checked request's tokens, and where its replay is split: before the
    middle token it was served."""
    return np.concatenate([prompt, out]), len(prompt) + len(out) // 2


def served_state(model, cfg, scheduler, request, seed, params, reference):
    """``(errors, rows)`` of the stopped ``scheduler``'s own programs on its
    own cache over ``request = (prompt, served tokens)``."""
    return model.served_state_errors(cfg, scheduler, *split(*request), seed,
                                     params, reference)


def _judged(model, table):
    return {k: e for k, e in table.items() if k not in model.NOT_JUDGED}


def check(model, cfg, params, reference, seed, requests, state, pad=None):
    """``(bad, checks, mechanism errors, served state)``: ``requests`` the
    checked ``(prompt, served tokens)``, the first of them the one whose
    ``state = served_state(...)`` was read; ``bad`` names every limit that
    does not hold.  ``pad = (multiple, least)``: the reference runs on the
    sequence padded to a multiple, no shorter than ``least`` (one compiled
    reference for every short sequence), where the configuration's
    ``max_seq_len`` is far more than a checked sequence needs."""
    bad, checks = [], []
    held, held_rows = state
    errs = model.mechanism_errors(cfg, params, seed, reference)
    if not all(e <= model.MECHANISM_RTOL.get(k, 0.0)
               for k, e in _judged(model, errs).items()):
        bad.append("mechanisms vs reference: %s" % errs)
    fns = model.replay_fns(cfg)
    for n_req, (prompt, out) in enumerate(requests):
        P = len(prompt)
        if len(out) < 3:
            bad.append("prompt of %d served %d tokens: nothing to check"
                       % (P, len(out)))
            continue
        seq, at_split = split(prompt, out)
        logits, sets, first, end = model.replay(cfg, params, seq, at_split,
                                                seed, fns)
        # the reference computes rows first .. end - 1 (the last whole chunk,
        # the narrow one, the decoded tokens) over the experts the step
        # functions took; the chunk before them it routes by itself
        rows = list(range(first, end))
        lo = max(0, first - cfg["chunk"])
        at = np.unique(np.linspace(0, len(out) - 1,
                                   model.CHECKED_TOKENS).astype(int))
        n_logits = len(logits)
        positions = (list(range(end - n_logits, end))
                     + [P - 1 + j for j in at] + list(range(lo, end)))
        ref_cfg = cfg if pad is None else dict(
            cfg, max_seq_len=max(pad[1], -(-len(seq) // pad[0]) * pad[0]))
        ref_logits, ref_chosen, ref_rows = model.reference_logits(
            ref_cfg, params, seq, positions, reference, forced=(rows, sets))
        logit_err = [float(np.max(np.abs(a - b)) / b.std())
                     for a, b in zip(logits, ref_logits)]
        tok_gaps = np.asarray([model.gap(ref_logits[n_logits + n], out[j])
                               for n, j in enumerate(at)])
        tokens_agree = float((tok_gaps <= model.TIE_TOL).mean())
        own = n_logits + len(at) + first - lo     # the forced rows' places
        agree = model.routing_agreement(
            np.concatenate(sets), np.concatenate([r[own:] for r in ref_chosen]))
        if n_req == 0:
            held.update(model.deep_row_errors(
                cfg, first, held_rows,
                [r[n_logits + len(at):] for r in ref_rows]))
        checks.append({"prompt": P, "served": len(out),
                       "tokens_checked": len(at),
                       "tokens_agree": tokens_agree,
                       "token_gap_max": float(tok_gaps.max()),
                       "logit_err": logit_err, "routing": agree})
        if not tokens_agree >= model.TOKENS_AGREE:
            bad.append("prompt of %d: share of %d served tokens within %s "
                       "logit std of the f32 reference's top: %s"
                       % (P, len(at), model.TIE_TOL, tokens_agree))
        if not all(e <= model.LOGIT_TOL for e in logit_err):
            bad.append("prompt of %d: chunk and decode logits vs the f32 "
                       "reference over the same experts, max error in logit "
                       "std: %s" % (P, logit_err))
        if not agree[0] >= model.ROUTING_AGREE:
            bad.append("prompt of %d: routed experts vs the reference's "
                       "(share held, sets equal): %s" % (P, agree))
    if not (set(model.SERVED_STATE_TOL) <= set(held)
            and all(e <= model.SERVED_STATE_TOL.get(k, 0.0)
                    for k, e in _judged(model, held).items())):
        bad.append("the engine's own programs on its own cache: %s" % held)
    return bad, checks, errs, held
