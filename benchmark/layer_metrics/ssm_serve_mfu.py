import _common as c  # the harness puts this directory on the path


def read(obs, params):
    sizes = obs["sizes"]
    if "mamba_d_state" not in sizes:
        return None  # another architecture's cell
    flops = c.bench_module("flops_jamba")
    prefills, decoded = c.slice_tokens(obs)
    if not prefills and not decoded:
        return None
    total = sum(flops.prefill_flops(sizes, n) for n, _ in prefills)
    total += sum(flops.token_flops(sizes, ctx, logits=True) for ctx in decoded)
    peak = obs["peak"]["bf16_flops_per_s"] * obs["chips"]
    return 100.0 * total / (obs["trace"]["window_s"] * peak)
