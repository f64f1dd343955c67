import _common as c  # the harness puts this directory on the path


def read(obs, params):
    flops = c.bench_module("flops")
    seconds = sum(
        s for dev in obs["trace"]["devices"]
        for s in c.matching(dev["op_total_s"],
                            params["kernel_patterns"]).values())
    _, decoded = c.slice_tokens(obs)
    if not seconds or not decoded:
        return None
    least = sum(flops.decode_kv_bytes(obs["sizes"], ctx) for ctx in decoded) \
        / obs["peak"]["hbm_bytes_per_s"]
    return 100.0 * least / seconds
