import _common as c  # the harness puts this directory on the path


def read(obs, params):
    seconds = steps = 0.0
    for dev in obs["trace"]["devices"]:
        for row in c.matching(dev["programs"],
                              params["program_patterns"]).values():
            seconds += row["seconds"]
            steps += row["inner_loops"]
    return 1e3 * seconds / steps if steps else None
