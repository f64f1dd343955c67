import _common as c  # the harness puts this directory on the path


def read(obs, params):
    trace = obs["trace"]
    seconds = sum(row["seconds"] for dev in trace["devices"]
                  for row in c.matching(dev["programs"],
                                        params["program_patterns"]).values())
    if not seconds:
        return None
    return 100.0 * seconds / len(trace["devices"]) / trace["window_s"]
