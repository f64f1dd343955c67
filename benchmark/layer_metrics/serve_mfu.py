import _common as c  # the harness puts this directory on the path


def read(obs, params):
    flops = c.bench_module("flops")
    sizes = obs["sizes"]
    prefills, decoded = c.slice_tokens(obs)
    if not prefills and not decoded:
        return None
    # tokens the prefix cache served were not computed: the counter says
    # how many, the traffic which prompts began with shared tokens. The
    # share is the whole window's (the counter moves at admission, a first
    # frame comes later: over a slice of seconds the two part at its edges)
    saved = c.counter_delta(obs, "prefix", "prefill_tokens_saved")
    sharable = sum(r.request.shared for r in c.window_records(obs))
    if saved > sharable:
        raise ValueError(f"the prefix cache counted {saved} prefill tokens "
                         f"saved in the window, its requests shared {sharable}")
    hit = saved / sharable if sharable else 0.0
    total = sum(flops.prefill_flops(sizes, int(hit * s), n)
                for n, s in prefills)
    total += sum(flops.token_flops(sizes, ctx, logits=True) for ctx in decoded)
    peak = obs["peak"]["bf16_flops_per_s"] * obs["chips"]
    return 100.0 * total / (obs["trace"]["window_s"] * peak)
