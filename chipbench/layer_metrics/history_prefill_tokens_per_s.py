"""Set-up's prompt tokens over the seconds its chunk programs took (the sum of
the span ``serving.decode.prefill`` during set-up, dispatch to readback): the
rate at which the standing contexts were prefilled."""


def read(observed):
    setup = observed.get("setup") or {}
    if not setup.get("prefill_s"):
        return None
    return setup["prompt_tokens"] / setup["prefill_s"]
