import _common as c  # the harness puts this directory on the path


def read(obs, params):
    sizes = obs["sizes"]
    if "mamba_d_state" not in sizes:
        return None  # another architecture's cell
    flops = c.bench_module("flops_jamba")
    seconds = steps = 0.0
    for dev in obs["trace"]["devices"]:
        for row in c.matching(dev["programs"],
                              params["program_patterns"]).values():
            seconds += row["seconds"]
            steps += row["inner_loops"]
    _, decoded = c.slice_tokens(obs)
    if not seconds or not steps:
        return None
    least = flops.decode_bytes(sizes, steps, decoded) \
        / obs["peak"]["hbm_bytes_per_s"]
    return 100.0 * least / seconds
