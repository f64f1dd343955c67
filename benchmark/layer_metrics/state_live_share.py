import _common as c  # the harness puts this directory on the path


def read(obs, params):
    try:
        live = c.counter_delta(obs, "state", "state_rows_live")
        swept = c.counter_delta(obs, "state", "state_rows_swept")
    except KeyError:
        return None  # a program without the sweep's counters
    return 100.0 * live / swept if swept else None
