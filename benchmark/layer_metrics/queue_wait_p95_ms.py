import _common as c  # the harness puts this directory on the path


def read(obs, params):
    waits = [m["t_s"] * 1e3 for j in c.window_journeys(obs)
             if (m := c.first_mark(j, "admit")) is not None]
    if not waits:
        return None
    return c.bench_module("load").percentile(waits, 95)
