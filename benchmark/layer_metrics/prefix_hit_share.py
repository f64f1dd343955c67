import _common as c  # the harness puts this directory on the path


def read(obs, params):
    sent = sum(len(r.request.prompt) for r in c.window_records(obs))
    if not sent:
        return None
    return 100.0 * c.counter_delta(obs, "prefix", "prefill_tokens_saved") / sent
