"""The scanned layer stack carries its KV rings and writes them in place.

In decode mode ``Stack.apply`` keeps each attention kind's stacked ring in
the layer scan's carry and writes only the new tokens' entries, at the
layer's group index.  The same model run unscanned (``scan_layers=False``:
every layer its own ring, no stack) is the reference: after a ragged
``extend`` and a few decode steps, both give the same logits and their
rings hold the same entries.  Cases cover both head widths the benchmark
serves (64, 128), a sliding window whose ring wraps, the encoder-decoder
stack (a read-only cross cache beside the carried ring), and the MoE and
hybrid recurrent stacks (states that stay on the scan's xs/ys).
"""

import dataclasses

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.configs.registry import ARCHS
from repro.models.model_zoo import build_model

# name -> (zoo arch, config overrides, max_len, prompt lengths, chunk)
CASES = {
    # OPT's block: 64-wide heads, two layers per scan step, one rest layer
    "full-hd64": ("olmo-1b", dict(head_dim=64, n_layers=5, scan_unroll=2,
                                  act="relu", glu=False), 32, (9, 4), 12),
    "full-hd128": ("olmo-1b", dict(head_dim=128, n_layers=4, scan_unroll=1),
                   32, (12, 7), 12),
    # window 32: the prompt and decode run past it, so the ring recycles
    "swa-wraps": ("h2o-danube-3-4b", dict(n_layers=4, scan_unroll=1), 64,
                  (30, 22), 30),
    "attn-cross": ("seamless-m4t-medium", dict(n_layers=4, scan_unroll=1),
                   32, (10, 6), 10),
    "moe": ("granite-moe-1b-a400m", dict(n_layers=4, scan_unroll=1), 32,
            (8, 11), 11),
    "hybrid": ("recurrentgemma-2b", dict(n_layers=6, scan_unroll=1), 48,
               (10, 5), 10),
}
DECODE_STEPS = 6


def _unscan(stack, tree):
    """A scanned stack's params or caches in the unscanned layout: one entry
    per layer, in execution order (group-major, then pattern position)."""
    rest = [jax.tree.map(lambda a, g=g: a[g], tree["groups"][pos])
            for g in range(stack.n_groups) for pos in range(stack.period)]
    return {"groups": [], "rest": rest + list(tree["rest"])}


def _unscan_params(model, params):
    out = dict(params, decoder=_unscan(model.decoder, params["decoder"]))
    if model.encoder is not None:
        out["encoder"] = _unscan(model.encoder, params["encoder"])
    return out


def _unscan_state(model, state):
    return dict(state, caches=_unscan(model.decoder, state["caches"]))


def _close(got, want):
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("case", sorted(CASES))
def test_carried_ring_matches_unscanned_stack(case):
    arch, over, max_len, lengths, chunk = CASES[case]
    cfg = dataclasses.replace(ARCHS[arch].reduced(), scan_layers=True,
                              **over)
    scanned = build_model(cfg)
    plain = build_model(dataclasses.replace(cfg, scan_layers=False))
    assert scanned.decoder.n_groups >= 2 and plain.decoder.n_groups == 0
    params = scanned.init(jax.random.PRNGKey(11))
    params_u = _unscan_params(scanned, params)

    B = len(lengths)
    rng = np.random.RandomState(5)
    toks = rng.randint(0, cfg.vocab_size, (B, chunk)).astype(np.int32)
    lens = jnp.asarray(lengths, jnp.int32)
    if cfg.family == "encdec":
        # the cross cache is built by prefill from the encoder's output
        src = jnp.asarray(rng.standard_normal((B, 8, cfg.d_model)) * 0.02,
                          jnp.float32)
        head = {"tokens": jnp.asarray(toks[:, :4]), "src_embeds": src}
        lg_s, st_s = scanned.prefill(params, head, max_len=max_len)
        lg_u, st_u = plain.prefill(params_u, head, max_len=max_len)
        _close(lg_s, lg_u)
        toks, lens = toks[:, 4:], lens - 4
    else:
        st_s = scanned.init_decode_state(B, max_len)
        st_u = plain.init_decode_state(B, max_len)
    # a ragged chunk: each row extends its rings by its own length
    lg_s, st_s = jax.jit(scanned.extend)(params, st_s, jnp.asarray(toks),
                                         lengths=lens)
    lg_u, st_u = jax.jit(plain.extend)(params_u, st_u, jnp.asarray(toks),
                                       lengths=lens)
    _close(lg_s, lg_u)
    step_s, step_u = jax.jit(scanned.decode_step), jax.jit(plain.decode_step)
    for _ in range(DECODE_STEPS):
        tok = jnp.argmax(lg_s, axis=-1).astype(jnp.int32)[:, None]
        lg_s, st_s = step_s(params, st_s, tok)
        lg_u, st_u = step_u(params_u, st_u, tok)
        _close(lg_s, lg_u)

    got = jax.tree.leaves(_unscan_state(scanned, st_s))
    want = jax.tree.leaves(st_u)
    assert len(got) == len(want)
    for a, b in zip(got, want):
        assert a.shape == b.shape and a.dtype == b.dtype
        if a.dtype == jnp.int32:                  # ring positions, pos
            assert np.array_equal(np.asarray(a), np.asarray(b))
        else:
            _close(a, b)
    if case == "swa-wraps":
        # the window really wrapped: some ring slot holds a position past it
        pos = [np.asarray(a) for a in got if a.dtype == jnp.int32
               and a.ndim == 2]
        assert max(int(p.max()) for p in pos) >= 32
