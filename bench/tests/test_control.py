"""The check that decides ``correct`` fails what it must fail.

At a size the CPU holds: the control (the reference in the precision below
the configuration's, put in the program's place) reads a wider gap than
the program, and a run with the timed path broken underneath comes out
``correct: false`` for each fault a serving cell can have:

* a decode step that returns its state unchanged;
* half of the batch left out (those rows get the mean of the others);
* a token altered where it is produced (the sampler).

The cells take one chip, so there is no exchange between chips to leave
out.  On the chip the same control is read at the cells' own sizes with
``bench/limits.py`` (readings in ``PERF.md``).

    JAX_PLATFORMS=cpu python -m pytest bench/tests -q
"""

from __future__ import annotations

import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
for p in (HERE, BENCH, os.path.join(os.path.dirname(BENCH), "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

import limits  # noqa: E402
import run  # noqa: E402
import tiny  # noqa: E402

SEED = 2 ** 31 + 4242
# tiny-size limits on the mean logit gap, from CPU readings at this size
# over four seeds: program <= 0.0053 on the digit-serial path and <= 0.0002
# dense; control >= 0.027 and >= 0.0009; the faults read far above both
LIMIT = {"dslot": 0.015, "dense": 0.0006}


def _cell(path):
    found = tiny.cell("opt-1.3b", limit=LIMIT[path], dense=path == "dense")
    found["numbers"]["check_tokens"] = 96
    return found


def _run(found):
    return run.run(found["cell"]["name"], SEED, 3.0, False,
                   device_check=tiny.no_chip, found=found,
                   log=lambda m: None, cache=False)


@pytest.mark.parametrize("path", ["dslot", "dense"])
def test_control_reads_wider_than_the_program(path):
    r = limits.readings(_cell(path), SEED, 3.0)
    assert r["tokens"] >= 96
    assert r["mean_logit_gap"] <= LIMIT[path]
    assert r["control_mean_logit_gap"] > 3 * r["mean_logit_gap"]


@pytest.mark.parametrize("path", ["dslot", "dense"])
def test_sound_run_is_correct(path):
    out = _run(_cell(path))
    assert out["correct"] is True, out["checks"]


def _state_unchanged(monkeypatch):
    from repro.models.model_zoo import Model
    orig = Model.decode_step

    def stale(self, params, state, tokens):
        logits, _ = orig(self, params, state, tokens)
        return logits, state

    monkeypatch.setattr(Model, "decode_step", stale)


def _half_batch(monkeypatch):
    from repro.models.model_zoo import Model
    orig = Model.decode_step

    def half(self, params, state, tokens):
        logits, new = orig(self, params, state, tokens)
        keep = logits.shape[0] // 2
        return logits.at[keep:].set(logits[:keep].mean(0)), new

    monkeypatch.setattr(Model, "decode_step", half)


def _token_altered(monkeypatch):
    import jax.numpy as jnp
    from repro.serve import engine

    def wrong(logits, key=None):
        tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        return (tok + 1) % logits.shape[-1]

    monkeypatch.setattr(engine, "greedy_sample", wrong)


@pytest.mark.parametrize("path", ["dslot", "dense"])
@pytest.mark.parametrize("fault", [_state_unchanged, _half_batch,
                                   _token_altered],
                         ids=["state-unchanged", "half-batch",
                              "token-altered"])
def test_broken_timed_path_is_not_correct(monkeypatch, path, fault):
    fault(monkeypatch)
    out = _run(_cell(path))
    assert out["correct"] is False
    gap = out["checks"]["mean_logit_gap"]
    assert gap["value"] > gap["limit"]
