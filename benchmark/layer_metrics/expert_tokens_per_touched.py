import _common as c  # the harness puts this directory on the path


def read(obs, params):
    try:
        held = c.counter_delta(obs, "moe", "expert_pairs_held")
        touched = c.counter_delta(obs, "moe", "experts_touched")
    except KeyError:
        return None  # a program without the routing counters
    return held / touched if touched else None
