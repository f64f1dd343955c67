import _program as p  # the harness puts this directory on the path


def read(obs, params):
    by_core: dict = {}
    for r in p.window_dispatches(obs):
        if r["steps"] > 0:
            acc = by_core.setdefault(r["model"], [0, 0])
            acc[0] += r["rows"] * r["steps"]
            acc[1] += r["steps"]
    if not by_core:
        return None
    return sum(rows / steps for rows, steps in by_core.values())
