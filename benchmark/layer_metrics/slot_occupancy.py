def read(obs, params):
    t0, t1 = obs["t0"], obs["t0"] + obs["seconds"]
    held = 0.0
    for r in obs["records"]:
        if len(r.frames) > 1:
            held += max(0.0, min(r.frames[-1][0], t1) - max(r.frames[0][0], t0))
    if not held:
        return None
    return 100.0 * held / (obs["seconds"] * obs["batch_slots"] * obs["chips"])
