"""A whole run of each small cell on the CPU, with the timed path broken
underneath: ``correct`` must come out false for each fault the cell can
have, and true with nothing broken.

The serving cells are the real drivers over the program's ``ServingLoop``
at a 2-layer, 64-wide configuration (``data/``); the kernel cell runs the
program's Pallas kernels in the interpreter at small sizes.  Faults are
planted in the program's functions that the timed path calls."""
import os

import jax.numpy as jnp
import pytest

from chipbench.harness import HERE, Manifest, run_cell

DATA = os.path.join(os.path.dirname(__file__), "data")
M = Manifest(os.path.join(DATA, "BENCHMARK.json"), search=(DATA, HERE))
SECONDS = 1.0
SEED = 2 ** 36 + 17


def _run(cell):
    return run_cell(cell, SEED, SECONDS, False, manifest=M,
                    require_tpu=False)


def _state_unchanged(fn):
    def broken(params, cfg, tokens, paged, *a):
        logits, _ = fn(params, cfg, tokens, paged, *a)
        return logits, paged
    return broken


def _token_altered(fn):
    def broken(*a):
        logits, paged = fn(*a)
        return jnp.roll(logits, 1, axis=-1), paged
    return broken


def _half_batch_dropped(fn):
    def broken(*a):
        logits, paged = fn(*a)
        half = logits.shape[0] // 2
        return logits.at[half:].set(0.0), paged
    return broken


SERVE_FAULTS = {
    "state_unchanged": ("forward_paged_decode", _state_unchanged),
    "token_altered": ("forward_paged_decode", _token_altered),
    "half_batch_dropped": ("forward_paged_decode", _half_batch_dropped),
    "chunk_state_unchanged": ("forward_paged_chunk", _state_unchanged),
}


@pytest.mark.parametrize("cell", ["tiny-dense.batch", "tiny-dense.prefix"])
def test_sound_serving_run_is_correct(cell):
    line = _run(cell)
    assert line["correct"] is True
    assert line["failed"] == 0 and line["attempted"] > 0
    assert line["checks"]["max_gap"]["value"] <= \
        line["checks"]["max_gap"]["limit"]


@pytest.mark.parametrize("fault", sorted(SERVE_FAULTS))
def test_serving_fault_is_caught(fault, monkeypatch):
    from repro.models import transformer
    name, breaker = SERVE_FAULTS[fault]
    monkeypatch.setattr(transformer, name,
                        breaker(getattr(transformer, name)))
    cell = "tiny-dense.prefix" if "chunk" in fault else "tiny-dense.batch"
    assert _run(cell)["correct"] is False


def test_kernel_answer_altered_is_caught(monkeypatch):
    from repro.kernels import ops
    real = ops.hotspot
    monkeypatch.setattr(ops, "hotspot", lambda *a, **k: real(
        *a, **k).at[3, 5].add(1e-3))
    line = _run("tiny-kernels.two")
    assert line["correct"] is False
    assert line["checks"]["hotspot_rel_err"]["value"] > 0


def test_kernel_state_unchanged_is_caught(monkeypatch):
    from repro.kernels import ops
    monkeypatch.setattr(ops, "stream", lambda x, **k: x)
    line = _run("tiny-kernels.two")
    assert line["correct"] is False
    assert line["checks"]["stream_rel_err"]["value"] > 0
    assert line["checks"]["hotspot_rel_err"]["value"] == 0


def test_sound_kernel_run_is_correct():
    line = _run("tiny-kernels.two")
    assert line["correct"] is True
    assert set(line["metrics"]) == {"call_ms", "setup_s"}
