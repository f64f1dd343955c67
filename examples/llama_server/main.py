"""Llama chat serving — BASELINE.md config #4's serving surface.

One continuous-batching Generator behind three transports, the same
handler-per-transport shape as the reference (handler.go:27-38):

- ``POST /generate``            -> full completion (JSON)
- ``WS   /stream``              -> token-at-a-time frames to browsers
- gRPC ``llm.Chat/Generate``    -> server-streaming JSON frames on :9000

Model size comes from env (LLAMA_PRESET=tiny|1b|8b) so the same example runs
on CPU tests and on real chips.
"""

import os

import gofr_tpu
from gofr_tpu.grpc import JSONService
from gofr_tpu.ml.generate import Sampler, spec_k_from_env
from gofr_tpu.ml.scheduler import normalize_priority
from gofr_tpu.models import llama
from gofr_tpu.native.tokenizer import BPETokenizer

# byte-level fallback vocabulary; a real vocab loads from the checkpoint
# dir's tokenizer.json (LLAMA_CKPT) or TOKENIZER_JSON in main()
TOKENIZER = BPETokenizer.byte_level(specials=["<eos>"])


def _tokenizer_from_env() -> BPETokenizer:
    tk = os.environ.get("TOKENIZER_JSON")
    ckpt = os.environ.get("LLAMA_CKPT")
    if not tk and ckpt and os.path.isfile(os.path.join(ckpt, "tokenizer.json")):
        tk = os.path.join(ckpt, "tokenizer.json")
    if tk:
        from gofr_tpu.ml.hf_import import load_hf_tokenizer

        return load_hf_tokenizer(tk)
    return BPETokenizer.byte_level(specials=["<eos>"])


def _prompt_ids(body) -> list[int]:
    if body.get("prompt_ids"):
        return body["prompt_ids"]
    if body.get("prompt"):
        return TOKENIZER.encode(body["prompt"])
    raise gofr_tpu.errors.MissingParam("prompt or prompt_ids")


def _admissible(llm, ids, max_new) -> None:
    """Un-admittable prompts answer 400 (HTTP) / INVALID_ARGUMENT (gRPC)
    before any stream opens, not a 500 after admission fails."""
    try:
        llm.check_admissible(ids, max_new)
    except ValueError as exc:
        raise gofr_tpu.errors.InvalidInput(str(exc)) from exc


def _priority(body) -> int:
    """Admission class from the request body (``"priority": "high" |
    "normal" | "low"``); unknown values answer 400, not a demotion."""
    try:
        return normalize_priority(body.get("priority"))
    except ValueError as exc:
        raise gofr_tpu.errors.InvalidInput(str(exc)) from exc


def _deadline(body):
    """Per-request TTL from the body (``"deadline_s": 2.5``): past it the
    server reaps the request — queued or mid-decode — with a 504 /
    DEADLINE_EXCEEDED. None defers to GOFR_ML_DEFAULT_DEADLINE_S."""
    raw = body.get("deadline_s")
    if raw is None:
        return None
    import math

    try:
        deadline = float(raw)
        if not math.isfinite(deadline) or deadline < 0:
            raise ValueError
    except (TypeError, ValueError):
        raise gofr_tpu.errors.InvalidInput(
            f"deadline_s must be a finite number >= 0, got {raw!r}") from None
    return deadline


async def generate(ctx: gofr_tpu.Context):
    body = await ctx.bind()
    ids = _prompt_ids(body)
    max_new = int(body.get("max_new_tokens", 64))
    llm = ctx.ml.llm("chat")
    _admissible(llm, ids, max_new)
    tokens = await llm.generate(ids, max_new, priority=_priority(body),
                                deadline_s=_deadline(body))
    out = {"tokens": tokens}
    if body.get("prompt"):  # text in -> text out
        out["text"] = TOKENIZER.decode(tokens)
    return out


async def stream_ws(ctx: gofr_tpu.Context):
    body = await ctx.bind()
    ids = _prompt_ids(body)
    llm = ctx.ml.llm("chat")
    max_new = int(body.get("max_new_tokens", 64))
    _admissible(llm, ids, max_new)
    async for tok in llm.stream(ids, max_new, priority=_priority(body),
                                deadline_s=_deadline(body)):
        await ctx.write_message_to_socket({"token": tok})
    return {"done": True}


def build_app(params, cfg, **llm_kwargs) -> gofr_tpu.App:
    """The serving surface over one model: ``register_llm("chat", ...)``
    plus the three transports. ``main`` sizes the model from the
    environment; ``chip_smoke.py`` passes Llama-3-8B widths."""
    app = gofr_tpu.new_app()
    app.register_llm("chat", params, cfg, **llm_kwargs)

    app.post("/generate", generate)
    app.websocket("/stream", stream_ws)

    svc = JSONService("llm.Chat")

    async def grpc_generate(request, context):
        # one frame per decode-chunk burst, not per token: 16x fewer gRPC
        # messages at chunk=16 with identical token latency (tokens arrive
        # from the device in bursts anyway)
        llm = app.container.ml.llm("chat")
        max_new = int(request.get("max_new_tokens", 64))
        _admissible(llm, request["prompt_ids"], max_new)
        async for burst in llm.stream_chunks(request["prompt_ids"],
                                             max_new,
                                             priority=_priority(request),
                                             deadline_s=_deadline(request)):
            yield {"tokens": burst}

    svc.stream("Generate", grpc_generate)
    app.register_service(svc, impl=None)
    return app


def main() -> gofr_tpu.App:
    global TOKENIZER
    TOKENIZER = _tokenizer_from_env()
    # LLAMA_PRESET / LLAMA_KV_QUANT / LLAMA_W8 / LLAMA_CKPT -> config
    # (shared with openai_server; a HF checkpoint defines the arch)
    cfg = llama.config_from_env(tiny_vocab_size=TOKENIZER.vocab_size)
    params = llama.params_from_config(cfg)
    # LLM_SPEC_K, falling back to the framework-wide GOFR_ML_SPEC_K
    # knob — the fallback goes through the loudly-validated parse
    # (named error at boot), and the Generator re-validates the
    # final value either way
    raw_spec = os.environ.get("LLM_SPEC_K", "").strip()
    spec_k = int(raw_spec) if raw_spec else spec_k_from_env()
    draft_params, draft_cfg = (llama.draft_from_env(cfg, params)
                               if spec_k else (None, None))
    # LLM_DISAGG validates LOUDLY like GOFR_ML_DISAGG would: a typo'd
    # value must not silently boot aggregated (and override the env)
    raw_disagg = os.environ.get("LLM_DISAGG", "").strip()
    if raw_disagg and raw_disagg not in ("0", "1"):
        raise ValueError(f"LLM_DISAGG must be 0 or 1, got {raw_disagg!r}")
    return build_app(
        params, cfg,
        batch_slots=int(os.environ.get("LLM_SLOTS", "4")),
        max_seq=min(cfg.max_seq_len, 1024),
        chunk=int(os.environ.get("LLM_CHUNK", "4")),
        sampler=Sampler(temperature=float(os.environ.get("LLM_TEMPERATURE", "0"))),
        # real checkpoints carry their stop id (hf_config); random-weight
        # presets keep decoding to max_new (any id is as likely as eos)
        eos_id=getattr(cfg, "eos_id", None),
        # LLM_SPEC_K>0: device-resident speculation inside the
        # continuous-batching chunk (greedy-only, lossless); drafts come
        # from LLM_DRAFT_CKPT/LLM_DRAFT_PRESET when set, else prompt lookup
        spec_k=spec_k,
        draft_params=draft_params, draft_cfg=draft_cfg,
        # LLM_PAGE_SIZE>0: block-paged KV pool (LLM_PAGES sizes it below
        # the dense worst case — more concurrent slots per HBM byte)
        # LLM_PREFILL_CHUNK>0: segmented prefill interleaved with decode
        # chunks — a long prompt can't stall live streams (TTFT jitter)
        prefill_chunk=int(os.environ.get("LLM_PREFILL_CHUNK", "0")),
        page_size=int(os.environ.get("LLM_PAGE_SIZE", "0")),
        n_pages=int(os.environ.get("LLM_PAGES", "0")) or None,
        # LLM_DISAGG=1 (fallback: the framework-wide GOFR_ML_DISAGG knob,
        # which the replica pool reads itself) with GOFR_ML_REPLICAS>=2:
        # disaggregated prefill/decode over the KV transport — prompts
        # prefill on prefill-biased replicas, pages ship, decode replicas
        # admit suffix-only (paged generators only)
        **({"disagg": raw_disagg == "1"} if raw_disagg else {}),
    )


if __name__ == "__main__":
    main().run()
