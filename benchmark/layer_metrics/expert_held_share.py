import _common as c  # the harness puts this directory on the path


def read(obs, params):
    try:
        held = c.counter_delta(obs, "moe", "expert_pairs_held")
        routed = c.counter_delta(obs, "moe", "expert_pairs_routed")
    except KeyError:
        return None  # a program without the routing counters
    return 100.0 * held / routed if routed else None
