"""One run of one cell: load, warm up, measure, compare, build the line.

Driven by data: the cell's configuration, serve file, traffic mix and
per-layer metrics are files found by the names in ``BENCHMARK.json``; this
module holds no list of cells, configurations or metrics.
"""

from __future__ import annotations

import asyncio
import importlib.util
import json
import os
import shutil
import sys
import time

from . import contract, load, traffic, trace_reduce

TRACE_SLICE_S = 4.0       # traced seconds, in the middle of the window
DRAIN_S = 60.0            # how long past the close an answer is waited for


class NoAccelerator(RuntimeError):
    """No TPU, fewer chips than the cell asks for, or an unknown chip."""


def log(**fields) -> None:
    """An earlier line: facts and counts, on standard error."""
    print(json.dumps(fields, default=str), file=sys.stderr, flush=True)


def read_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


class CompileCounter:
    """Process-wide counts of jax's own compile events: the serving thread
    compiles too (copied from ``chip_smoke.py``)."""

    def __init__(self) -> None:
        self.requests = self.hits = self.misses = 0
        self.compile_s = 0.0
        self.names: list = []   # (perf_counter, program) of every compile

    def install(self) -> None:
        import logging

        import jax
        import jax.monitoring as mon

        mon.register_event_listener(self._event)
        mon.register_event_duration_secs_listener(self._duration)
        # jax names what it compiles only in its log
        jax.config.update("jax_log_compiles", True)
        counter = self

        class Names(logging.Handler):
            def emit(self, record) -> None:
                msg = record.getMessage()
                if msg.startswith("Compiling "):
                    counter.names.append((time.perf_counter(),
                                          msg.split()[1]))

        for name in ("jax._src.interpreters.pxla", "jax._src.dispatch"):
            logger = logging.getLogger(name)
            logger.addHandler(Names())
            logger.propagate = False  # the names go to the run's own log

    def named_between(self, a: float, b: float) -> list:
        return [n for t, n in self.names if a <= t < b]

    def _event(self, name: str, **kw) -> None:
        if name == "/jax/compilation_cache/compile_requests_use_cache":
            self.requests += 1
        elif name == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif name == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def _duration(self, name: str, secs: float, **kw) -> None:
        if name == "/jax/core/compile/backend_compile_duration":
            self.compile_s += secs


def find_devices(chips: int, peaks: dict) -> tuple:
    """The chips this run may use and their row of the peaks table; raises
    where there is no TPU, too few chips, or an unknown kind."""
    import jax

    devices = jax.devices()
    platform = devices[0].platform
    if platform != "tpu":
        raise NoAccelerator(f"needs a TPU, jax found {platform!r}")
    if len(devices) < chips:
        raise NoAccelerator(f"the cell needs {chips} chip(s), jax found "
                            f"{len(devices)}")
    kind = devices[0].device_kind
    if kind not in peaks["by_device_kind"]:
        raise NoAccelerator(f"device kind {kind!r} is not in peaks.json: "
                            f"{sorted(peaks['by_device_kind'])}")
    return devices[:chips], peaks["by_device_kind"][kind]


def enable_compile_cache(repo_root: str) -> str:
    """jax's persistent cache at the directory the environment names, or
    else at a fixed one inside the checkout. Both jax and the program are
    told: the program sets a directory of its own unless the variable is
    set, and jax reads the variable only when it is imported."""
    path = (os.environ.get("JAX_COMPILATION_CACHE_DIR")
            or os.path.join(repo_root, ".jax_cache"))
    os.environ["JAX_COMPILATION_CACHE_DIR"] = path
    import jax

    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def load_module(bench_dir: str, rel: str):
    """A file of the benchmark that a data file names (an entry, a
    configuration's weights or its reference), loaded from its path."""
    spec = importlib.util.spec_from_file_location(
        "benchmark_" + "".join(c if c.isalnum() else "_" for c in rel),
        os.path.join(bench_dir, rel + ".py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def load_reader(root: str, name: str):
    """A per-layer metric's own files: ``<name>.json`` and its reader."""
    here = os.path.join(root, "layer_metrics")
    if here not in sys.path:
        sys.path.insert(0, here)  # the readers share ``_common``
    meta = read_json(os.path.join(here, f"{name}.json"))
    return meta, load_module(here, name).read


# ------------------------------------------------------------ the window
def _warmup_requests(serve: dict, mix: dict, vocab: int, seed: int) -> list:
    """Waves run one after another before the pre-roll, so that every
    program the window will use has run once. The serve file lists them
    (``warmup``), because which shapes exist is the deployment's matter:
    ``prompt_tokens`` are sent alone, one per prefill bucket; ``burst``
    concurrent requests fill an admission wave; each of ``prefixes`` is a
    shared prefix of ``tokens`` sent once per entry of ``suffixes`` and
    twice more before (a prefix registers at its second sighting and is
    borrowed from its third), so that suffix prefill runs in every bucket.
    A cell of several replicas sends every wave once per replica, each
    copy with content of its own: the pool routes what no replica holds
    to the least loaded, so each replica gets one and builds its own
    programs (the compile cache's key holds the device)."""
    import numpy as np

    rng = np.random.default_rng([int(seed), 0xAA17])
    copies = int(serve["chips"])

    def ids(n):
        return rng.integers(1, vocab, int(n)).tolist()

    plan = serve["warmup"]
    ans = int(mix["answer_tokens"]["min"])
    mk = traffic.Request
    # a request sent alone asks for one token: its prefill is what it
    # warms; the burst decodes
    waves = [[mk(None, ids(n), 1, -1, 0, 0) for _ in range(copies)]
             for n in plan["prompt_tokens"]]
    if plan.get("burst"):
        n = plan["prompt_tokens"][0]
        waves.append([mk(None, ids(n), ans, -1, 0, 0)
                      for _ in range(int(plan["burst"]) * copies)])
    for pre in plan.get("prefixes", []):
        prefixes = [ids(pre["tokens"]) for _ in range(copies)]
        for n in [pre["suffixes"][0]] * 2 + list(pre["suffixes"]):
            waves.append([mk(None, p + ids(n), 1, -1, 0, len(p))
                          for p in prefixes])
    return waves


def _trace_slice(out_dir: str) -> dict:
    """Runs in a worker thread: trace ``TRACE_SLICE_S`` seconds with the
    marker annotation spanning them; the slice's ends on the host's clock
    are what the client's stamps are held against."""
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(out_dir, profiler_options=opts)
    try:
        with jax.profiler.TraceAnnotation(trace_reduce.MARKER):
            h0 = time.perf_counter()
            time.sleep(TRACE_SLICE_S)
            h1 = time.perf_counter()
    finally:
        jax.profiler.stop_trace()
    return {"h0": h0, "h1": h1}


async def _warm(client, warm_waves, compiles: CompileCounter) -> None:
    t_w = time.perf_counter()
    for wave in warm_waves:
        recs = await asyncio.gather(*[
            client.send(load.Record(r, time.perf_counter())) for r in wave])
        bad = [r.error or f"{len(r.tokens)} tokens" for r in recs if not r.ok]
        if bad:
            raise RuntimeError(f"a warm-up request failed: {bad[:3]}")
    log(step="warmup_wave", wall_s=time.perf_counter() - t_w,
        requests=sum(len(w) for w in warm_waves),
        programs_built=compiles.requests)


async def _window(system, client, plan, traced: bool, trace_dir: str,
                  compiles: CompileCounter) -> dict:
    """The pre-roll, the measured window and the wait for its answers."""
    loop = asyncio.get_running_loop()
    t0 = time.perf_counter() + plan.preroll_s + 0.05
    close = t0 + plan.seconds
    marks: dict = {}

    async def at(when: float, key: str) -> None:
        await asyncio.sleep(max(0.0, when - time.perf_counter()))
        marks[key] = {"counters": system.counters(),
                      "compiles": compiles.requests,
                      "time": time.perf_counter()}

    async def traced_slice() -> dict:
        start = t0 + (plan.seconds - TRACE_SLICE_S) / 2.0
        await asyncio.sleep(max(0.0, start - time.perf_counter()))
        return await loop.run_in_executor(None, _trace_slice, trace_dir)

    side = [asyncio.ensure_future(at(t0, "start")),
            asyncio.ensure_future(at(close, "end"))]
    slicer = asyncio.ensure_future(traced_slice()) if traced else None
    records = await load.run(client, plan, t0, close + DRAIN_S)
    await asyncio.gather(*side)
    obs = {"t0": t0, "records": records, "marks": marks,
           "seconds": plan.seconds,
           "drained_s": time.perf_counter() - close}
    if traced:
        obs["slice"] = await slicer
        obs["journeys"] = system.journeys()
    return obs


async def _drive(system, plans: list, warm_waves, vocab: int, traced: bool,
                 trace_dir: str, compiles: CompileCounter,
                 t_process: float) -> list:
    """Start the app, warm it up, run each plan's window in turn (a run of
    the benchmark has one; the sweep tool several), stop the app."""
    await system.start()
    client = load.Client(system.grpc_port, vocab)
    out = []
    try:
        await _warm(client, warm_waves, compiles)
        for plan in plans:
            obs = await _window(system, client, plan, traced, trace_dir,
                                compiles)
            obs["setup_s"] = obs["t0"] - t_process
            obs["memory_peak_bytes"] = memory_peak_bytes(system)
            out.append(obs)
    finally:
        await client.close()
        await system.shutdown()
    return out


def memory_peak_bytes(system) -> int:
    """The peak on the fullest of the chips the cell's replicas live on."""
    import jax

    devs = {d for core in system.cores
            for d in jax.tree.leaves(core.gen.params)[0].devices()}
    return max(d.memory_stats()["peak_bytes_in_use"] for d in devs)


# ------------------------------------------------------------- numbers
def _tokens_by_bin(obs: dict, seconds: float, width: float) -> list:
    """Tokens that reached a client in each ``width`` seconds of the
    window: where a run that reads far off lost or gained them."""
    bins = [0] * max(1, int(-(-seconds // width)))
    for r in obs["records"]:
        for t, n in r.frames:
            if 0 <= t - obs["t0"] < seconds:
                bins[int((t - obs["t0"]) // width)] += n
    return bins


def end_to_end(obs: dict, seconds: float) -> dict:
    """What a client sees, over all requests due in the window."""
    t0 = obs["t0"]
    recs = load.in_window(obs["records"], t0, seconds)
    give_up = t0 + seconds + DRAIN_S
    ttft = [((r.first if r.first is not None and r.ok else give_up) - r.due)
            * 1e3 for r in recs]
    tpot = [(r.frames[-1][0] - r.frames[0][0]) * 1e3 / (len(r.tokens) - r.frames[0][1])
            for r in recs
            if r.ok and len(r.frames) > 1 and len(r.tokens) > r.frames[0][1]]
    tokens = sum(n for r in obs["records"] for t, n in r.frames
                 if t0 <= t < t0 + seconds)
    out = {"tokens_per_s": tokens / seconds, "setup_s": obs["setup_s"]}
    if ttft:
        out["ttft_p50_ms"] = load.percentile(ttft, 50)
        out["ttft_p95_ms"] = load.percentile(ttft, 95)
    if tpot:
        out["tpot_p95_ms"] = load.percentile(tpot, 95)
    return out


def pick_sample(recs: list, n: int, seed: int) -> list:
    """Requests to hold against the reference: the longest context, the
    longest answer, the deepest turn on a shared prefix, and the rest
    drawn from the seed."""
    import numpy as np

    done = [r for r in recs if r.ok]
    if not done:
        return []
    picks = [max(done, key=lambda r: len(r.request.prompt) + len(r.tokens)),
             max(done, key=lambda r: len(r.tokens)),
             max(done, key=lambda r: (r.request.shared > 0, r.request.turn,
                                      r.request.shared))]
    rng = np.random.default_rng([int(seed), 0xC0DE])
    for i in rng.permutation(len(done)):
        if len(picks) >= n:
            break
        picks.append(done[int(i)])
    out, seen = [], set()
    for r in picks:
        if id(r) not in seen:
            seen.add(id(r))
            out.append(r)
    return out[:n]


def compare(reference, params, sizes: dict, sample: list, *,
            control: bool = False, max_positions: int = 256) -> dict:
    """Over the sample's served tokens, the gap by which each served
    token's logit lies below the reference's best (``program``) and, with
    ``control``, the same for the token the control puts first at the
    same prompts and tokens (``control``). ``reference`` is the module
    the configuration names."""
    import numpy as np

    got: dict = {"program": []}
    if control:
        got["control"] = []
    for r in sample:
        one = reference.gaps(params, sizes, r.request.prompt, r.tokens,
                             control=control, max_positions=max_positions)
        got["program"].append(one["gaps"])
        if control:
            got["control"].append(one["control_gaps"])
    return {who: np.concatenate(g) if g else np.zeros((0,))
            for who, g in got.items()}


def decide(gaps, unanswered: int, limits: dict) -> tuple:
    """The one verdict, for the program's gaps and for the control's put
    in their place: each number compared beside its limit, and whether
    all hold."""
    compared = {
        "gap_max": [float(gaps.max()) if gaps.size else float("inf"),
                    limits["gap_max"]],
        "gap_mean": [float(gaps.mean()) if gaps.size else float("inf"),
                     limits["gap_mean"]],
        "unanswered": [int(unanswered), 0],
        "tokens_compared": [int(gaps.size), limits["min_tokens"]],
    }
    correct = (compared["gap_max"][0] <= limits["gap_max"]
               and compared["gap_mean"][0] <= limits["gap_mean"]
               and unanswered == 0
               and gaps.size >= limits["min_tokens"])
    return compared, bool(correct)


# ------------------------------------------------------------- one run
def prepare(*, repo_root: str, bench_dir: str, bench: dict, workload: str,
            seed: int, data_dir: str | None = None) -> dict:
    """Everything before the app starts: the cell's files, the chips, the
    weights from the seed, the system under test built and warmed by its
    own ``register_llm``. ``data_dir`` holds ``serve/`` and ``traffic/``
    (a test's toy sizes live beside the tests); code, readers and peaks
    are ``bench_dir``'s."""
    data_dir = data_dir or bench_dir
    cell = contract.cell(bench, workload)
    config = next(c for c in bench["configs"] if c["name"] == cell["config"])
    sizes = read_json(os.path.join(repo_root, config["file"]))
    serve = read_json(os.path.join(data_dir, "serve", f"{workload}.json"))
    mix = read_json(os.path.join(data_dir, "traffic",
                                 f"{cell['traffic']}.json"))
    peaks = read_json(os.path.join(bench_dir, "peaks.json"))
    if int(serve["chips"]) != int(cell["chips"]):
        raise ValueError(f"serve file says {serve['chips']} chips, the cell "
                         f"{cell['chips']}")
    devices, peak = find_devices(int(cell["chips"]), peaks)
    cache_dir = enable_compile_cache(repo_root)
    compiles = CompileCounter()
    compiles.install()
    need = traffic.longest_context(mix)
    if need > serve["register_llm"]["max_seq"]:
        raise ValueError(f"the mix's longest context {need} passes max_seq "
                         f"{serve['register_llm']['max_seq']}")

    # the architecture is the configuration's: its file names the module
    # that makes its weights and the one that is its plain reference
    weights = load_module(bench_dir, sizes["modules"]["weights"])
    reference = load_module(bench_dir, sizes["modules"]["reference"])
    t = time.perf_counter()
    params = weights.make(sizes, seed, sizes["torch_dtype"])
    log(step="weights", wall_s=time.perf_counter() - t, cache_dir=cache_dir,
        device_kind=devices[0].device_kind, chips=len(devices))
    t = time.perf_counter()
    entry = load_module(bench_dir, os.path.join("entries", serve["entry"]))
    system = entry.build(params, sizes, serve, devices)
    log(step="register_llm", wall_s=time.perf_counter() - t,
        programs_built=compiles.requests, cache_hits=compiles.hits,
        cache_misses=compiles.misses, backend_compile_s=compiles.compile_s)
    return {"sizes": sizes, "serve": serve, "mix": mix, "peak": peak,
            "devices": devices, "params": params, "system": system,
            "reference": reference, "compiles": compiles,
            "vocab": sizes["vocab_size"]}


def run_cell(*, repo_root: str, bench_dir: str, bench: dict, workload: str,
             seed: int, seconds: float, traced: bool, t_process: float,
             control: bool = False, data_dir: str | None = None) -> dict:
    """Returns the last line (validated by the caller before printing)."""
    ctx = prepare(repo_root=repo_root, bench_dir=bench_dir, bench=bench,
                  workload=workload, seed=seed, data_dir=data_dir)
    sizes, serve, mix, peak = (ctx["sizes"], ctx["serve"], ctx["mix"],
                               ctx["peak"])
    devices, params, system = ctx["devices"], ctx["params"], ctx["system"]
    compiles, vocab = ctx["compiles"], ctx["vocab"]

    plan = traffic.generate(mix, vocab, seconds, seed)
    warm = _warmup_requests(serve, mix, vocab, seed)
    trace_dir = os.path.join(repo_root, ".bench_trace", workload)
    obs, = asyncio.run(_drive(system, [plan], warm, vocab, traced, trace_dir,
                              compiles, t_process))
    obs.update(sizes=sizes, serve=serve, mix=mix, peak=peak,
               chips=len(devices), device_ids=[d.id for d in devices],
               batch_slots=system.batch_slots)
    recs = load.in_window(obs["records"], obs["t0"], seconds)
    failed = [r for r in recs if not r.ok]
    late = load.lateness_ms(obs["records"])
    log(step="window", attempted=len(recs), failed=len(failed),
        sent_in_all=len(obs["records"]), generator_lateness_ms=late,
        generator_starved=late["p99"] > 50.0, drained_s=obs["drained_s"],
        compiles_in_window=(obs["marks"]["end"]["compiles"]
                            - obs["marks"]["start"]["compiles"]),
        compiled_in_window=compiles.named_between(
            obs["marks"]["start"]["time"], obs["marks"]["end"]["time"]),
        first_errors=[r.error for r in failed[:3]],
        tokens_by_5s=_tokens_by_bin(obs, seconds, 5.0),
        kernel_branches=obs["marks"]["end"]["counters"]["kernel_branches"])
    want_branch = serve.get("decode_branch")
    if want_branch:
        table = obs["marks"]["end"]["counters"]["kernel_branches"]
        took = {k: v for k, v in table.items()
                if k.startswith(want_branch["op"] + "[")}
        if not took or any(v != want_branch["must_be"] for v in took.values()):
            raise RuntimeError(
                f"{want_branch['op']} had to take {want_branch['must_be']!r}, "
                f"the program recorded {took}: a fallback is never timed")

    if traced:
        metrics, breakdown, device_extra = per_layer(
            obs, bench, bench_dir, workload, trace_dir)
    else:
        e2e = end_to_end(obs, seconds)
        names = [m["name"] for m in contract.metrics_of(bench, workload, False)]
        metrics = {n: e2e[n] for n in names if n in e2e}
        breakdown, device_extra = None, {}

    # the comparison: after the window, the peak's reading and the
    # program's state freed, so the reference sets no peak of its own
    t = time.perf_counter()
    limits = serve["correct"]
    sample = pick_sample(recs, int(limits["sample"]), seed)
    gaps = compare(ctx["reference"], params, sizes, sample, control=control,
                   max_positions=int(mix["answer_tokens"]["max"]))
    unanswered = sum(1 for r in recs if not r.frames)
    compared, correct = decide(gaps["program"], unanswered, limits)
    import jax

    for leaf in jax.tree.leaves(params):
        leaf.delete()  # a caller that runs several seeds needs the room
    log(step="compare", wall_s=time.perf_counter() - t, requests=len(sample),
        longest_context=max((len(r.request.prompt) + len(r.tokens)
                             for r in sample), default=0),
        **{k: v[0] for k, v in compared.items()})
    units = {m["name"]: m["unit"]
             for m in bench["end_to_end"] + bench["per_layer"]}
    device = {"platform": devices[0].platform, "kind": devices[0].device_kind,
              "count": len(devices),
              "memory_peak_bytes": obs["memory_peak_bytes"], **device_extra}
    line = contract.build_line(
        correct=correct, attempted=len(recs), failed=len(failed),
        metrics=metrics, units=units, device=device, breakdown=breakdown,
        compared=compared)
    if control:
        # the control put in the program's place, through the same verdict
        c_compared, c_correct = decide(gaps["control"], unanswered, limits)
        line["control"] = {"correct": c_correct, "compared": c_compared}
    return line


def per_layer(obs: dict, bench: dict, bench_dir: str, workload: str,
              trace_dir: str) -> tuple:
    """The traced run's numbers: the trace reduced once, then each metric
    of this cell read by its own reader."""
    import glob

    paths = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")),
        key=os.path.getmtime)
    if not paths:
        raise trace_reduce.TraceError(f"the profiler wrote no trace under "
                                      f"{trace_dir}")
    t = time.perf_counter()
    reduced = trace_reduce.reduce(trace_reduce.load(paths[-1]),
                                  device_ids=obs["device_ids"])
    log(step="trace", file_bytes=os.path.getsize(paths[-1]),
        read_s=time.perf_counter() - t, window_s=reduced["window_s"],
        busy_s=reduced["busy_s"],
        per_device=[{"plane": d["plane"], "in_trace": d["in_trace"],
                     "busy_s": d["busy_s"], "events": d["events"]}
                    for d in reduced["devices"]],
        programs=trace_reduce.summed(reduced, "programs"))
    # a trace is read once; it and what the profiler wrote beside it go
    shutil.rmtree(os.path.join(trace_dir, "plugins"), ignore_errors=True)
    obs["trace"] = reduced
    metrics = {}
    for m in contract.metrics_of(bench, workload, True):
        meta, read = load_reader(bench_dir, m["name"])
        value = read(obs, meta.get("params", {}))
        if value is not None:
            metrics[m["name"]] = float(value)
        else:
            log(step="metric_silent", metric=m["name"])
    return (metrics, trace_reduce.breakdown(reduced),
            {"busy_s": reduced["busy_s"], "window_s": reduced["window_s"]})
