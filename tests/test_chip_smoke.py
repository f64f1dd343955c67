"""``chip_smoke.py`` off the chip: the script refuses to run, and its phase
functions — the same ones ``__main__`` runs at Llama-3-8B widths — pass at
``tiny_llama()`` on the CPU backend's virtual devices.
"""

import dataclasses
import json
import os
import subprocess
import sys
import time

import jax
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import chip_smoke  # noqa: E402
from gofr_tpu.ml.generate import Generator  # noqa: E402
from gofr_tpu.models import llama  # noqa: E402

TINY = chip_smoke.Sizes(batch_slots=4, max_seq=128, chunk=2, page_size=8,
                        prefill_chunk=16, prompt_lens=(10, 60), prefix_len=32,
                        max_new=8, kernels="xla")


@pytest.fixture(scope="module")
def model():
    cfg = llama.tiny_llama()
    return cfg, llama.init_params(cfg, jax.random.PRNGKey(0))


def test_refuses_without_a_chip():
    """No accelerator: non-zero at once, ``"ok": false`` last, and nothing
    was built first (the only line printed is the verdict)."""
    t0 = time.monotonic()
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "chip_smoke.py")],
        env={**os.environ, "JAX_PLATFORMS": "cpu"}, cwd=REPO,
        capture_output=True, text=True, timeout=120)
    assert proc.returncode != 0
    assert time.monotonic() - t0 < 60
    lines = proc.stdout.strip().splitlines()
    assert len(lines) == 1, proc.stdout
    verdict = json.loads(lines[0])
    assert verdict["ok"] is False
    assert verdict["device"]["platform"] == "cpu"


@pytest.mark.parametrize("phase", [chip_smoke.phase_serve,
                                   chip_smoke.phase_paged],
                         ids=["serve", "paged"])
def test_phase_passes_at_tiny_size(model, phase):
    cfg, params = model
    stats = phase(cfg, params, TINY)
    assert stats["requests"]["failed"] == 0
    assert stats["max_logit_shortfall"] <= TINY.tol
    assert set(stats["branches"].values()) == {"xla"}  # no TPU here


def test_reference_rejects_tokens_the_model_would_not_pick(model):
    """The logit check has teeth: the reference's own greedy tokens pass at
    zero shortfall, and the same answer with one token swapped for the
    position's least likely id raises."""
    import jax.numpy as jnp

    cfg, params = model
    prompt = list(range(1, 20))
    ids = list(prompt)
    for _ in range(4):
        logits = llama.forward(params, jnp.asarray([ids]), cfg)[0, -1]
        ids.append(int(jnp.argmax(logits)))
    served = ids[len(prompt):]
    check = chip_smoke.make_reference(cfg, TINY.tol)
    assert check(params, prompt, served) <= TINY.tol
    worst = int(jnp.argmin(llama.forward(
        params, jnp.asarray([ids[:-1]]), cfg)[0, -1]))
    with pytest.raises(chip_smoke.SmokeFailure):
        check(params, prompt, served[:-1] + [worst])


def test_replicas_phase_on_four_devices(model):
    cfg, params = model
    devices = tuple(jax.devices()[:4])
    stats = chip_smoke.phase_replicas(
        cfg, params, dataclasses.replace(TINY, devices=devices))
    assert stats["routed"] == {0: 4, 1: 4, 2: 4, 3: 4}
    assert [row["device"] for row in stats["placement"].values()] == [
        str(d) for d in devices]


@pytest.mark.parametrize("page_size", [0, 8], ids=["dense", "paged"])
def test_generator_state_is_born_on_its_params_device(model, page_size):
    """Before any jitted call has run, a one-chip replica's cache, token row
    and page table already live on the chip its params are committed to —
    not on chip 0 waiting to follow them."""
    cfg, params = model
    dev = jax.devices()[3]
    gen = Generator(jax.device_put(params, dev), cfg, batch_slots=2,
                    max_seq=64, page_size=page_size)
    state = [gen.cache, gen._tok_dev]
    if page_size:
        state.append(gen._table_device())
    assert {d for leaf in jax.tree.leaves(state)
            for d in leaf.devices()} == {dev}
