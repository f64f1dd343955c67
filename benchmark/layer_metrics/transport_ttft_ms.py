import _common as c  # the harness puts this directory on the path


def read(obs, params):
    load = c.bench_module("load")
    client = [(r.first - r.sent) * 1e3 for r in c.window_records(obs)
              if r.ok and r.first is not None]
    server = [m["t_s"] * 1e3 for j in c.window_journeys(obs)
              if (m := c.first_mark(j, "prefill")) is not None]
    if not client or not server:
        return None
    return load.percentile(client, 50) - load.percentile(server, 50)
