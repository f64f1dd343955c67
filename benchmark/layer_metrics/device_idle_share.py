def read(obs, params):
    t = obs["trace"]
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])
