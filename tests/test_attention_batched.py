"""Batched multi-token cache extension: the (B, S)-positions flash path.

PR 5 lifted the batch-1 restriction on multi-token cache extension
(``attention_forward`` S > 1 with a cache).  These tests pin the new
surface directly:

* the generic flash path with per-sequence 2-D positions against a dense
  per-sequence reference mask (causal, windowed, ring holes);
* 2-D positions broadcast from shared 1-D positions are bit-identical to
  the 1-D path (the serving pools rely on this);
* ragged extension's masked ring writes — a padded row's phantom positions
  can NEVER clobber live slots, even when they wrap the ring;
* the SWA carry-window extension: sliding-window stacks extend their rings
  chunk-by-chunk by attending against the carried pre-write ring alongside
  the chunk's own keys, so ring recycling can never evict a live in-window
  key — chunked extension matches whole-prompt prefill, and the ragged
  stacked SWA prefill builds each row's ring from its own last in-window
  keys (the per-row gather), not the padded batch's last columns.
"""

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.configs.registry import ARCHS
from repro.models.attention import flash_attention
from repro.models.model_zoo import build_model


def _dense_ref(q, k, v, q_pos, k_pos, *, causal=True, window=0):
    """Unchunked softmax attention with an explicit per-sequence mask."""
    B, Sq, Hq, D = q.shape
    Hkv = k.shape[2]
    G = Hq // Hkv
    qg = (np.asarray(q, np.float32) * D ** -0.5).reshape(B, Sq, Hkv, G, D)
    s = np.einsum("bqhgd,bkhd->bqhgk", qg, np.asarray(k, np.float32))
    mask = (k_pos >= 0)[:, None, :]
    if causal:
        mask = mask & (k_pos[:, None, :] <= q_pos[:, :, None])
    if window:
        mask = mask & (k_pos[:, None, :] > q_pos[:, :, None] - window)
    s = np.where(mask[:, :, None, None, :], s, -1e30)
    m = s.max(axis=-1, keepdims=True)
    p = np.exp(s - m)
    out = np.einsum("bqhgk,bkhd->bqhgd", p, np.asarray(v, np.float32)) \
        / p.sum(axis=-1)[..., None]
    return out.reshape(B, Sq, Hq, D)


def _rand_qkv(rng, B, Sq, Sk, Hq=4, Hkv=2, D=8):
    q = rng.standard_normal((B, Sq, Hq, D)).astype(np.float32)
    k = rng.standard_normal((B, Sk, Hkv, D)).astype(np.float32)
    v = rng.standard_normal((B, Sk, Hkv, D)).astype(np.float32)
    return q, k, v


def test_flash_2d_positions_matches_dense_reference():
    """Per-sequence (B, Sq) query positions at ragged offsets against a
    ring-ordered KV set with holes (-1 slots), multiple scan chunks."""
    rng = np.random.default_rng(0)
    B, Sq, Sk = 3, 5, 16
    q, k, v = _rand_qkv(rng, B, Sq, Sk)
    offsets = np.asarray([0, 4, 9], np.int32)
    q_pos = offsets[:, None] + np.arange(Sq, dtype=np.int32)[None]
    # each row's ring: positions scattered mod Sk, with holes beyond the
    # row's own frontier (never-written slots = -1)
    k_pos = np.full((B, Sk), -1, np.int32)
    for b in range(B):
        frontier = int(offsets[b]) + Sq          # keys written so far
        for p in range(frontier):
            k_pos[b, p % Sk] = p
    out = flash_attention(jnp.asarray(q), jnp.asarray(k), jnp.asarray(v),
                          jnp.asarray(q_pos), jnp.asarray(k_pos),
                          causal=True, window=0, chunk=4)
    ref = _dense_ref(q, k, v, q_pos, k_pos, causal=True, window=0)
    np.testing.assert_allclose(np.asarray(out), ref, atol=2e-5, rtol=2e-5)


def test_flash_2d_positions_windowed_matches_dense_reference():
    """Sliding-window masking composes with per-sequence positions."""
    rng = np.random.default_rng(1)
    B, Sq, Sk, W = 2, 4, 12, 5
    q, k, v = _rand_qkv(rng, B, Sq, Sk)
    offsets = np.asarray([3, 7], np.int32)
    q_pos = offsets[:, None] + np.arange(Sq, dtype=np.int32)[None]
    k_pos = np.full((B, Sk), -1, np.int32)
    for b in range(B):
        for p in range(int(offsets[b]) + Sq):
            k_pos[b, p % Sk] = p
    out = flash_attention(jnp.asarray(q), jnp.asarray(k), jnp.asarray(v),
                          jnp.asarray(q_pos), jnp.asarray(k_pos),
                          causal=True, window=W, chunk=4)
    ref = _dense_ref(q, k, v, q_pos, k_pos, causal=True, window=W)
    np.testing.assert_allclose(np.asarray(out), ref, atol=2e-5, rtol=2e-5)


def test_flash_2d_broadcast_equals_shared_1d_bitwise():
    """Broadcasting shared positions to (B, S) must not change a single
    bit — serving mixes both forms and exactness tests compare across."""
    rng = np.random.default_rng(2)
    B, Sq, Sk = 2, 6, 10
    q, k, v = _rand_qkv(rng, B, Sq, Sk)
    q_pos = np.arange(Sq, dtype=np.int32)
    k_pos = np.arange(Sk, dtype=np.int32)
    o1 = flash_attention(jnp.asarray(q), jnp.asarray(k), jnp.asarray(v),
                         jnp.asarray(q_pos), jnp.asarray(k_pos),
                         causal=True, window=0, chunk=4)
    o2 = flash_attention(jnp.asarray(q), jnp.asarray(k), jnp.asarray(v),
                         jnp.broadcast_to(jnp.asarray(q_pos)[None], (B, Sq)),
                         jnp.broadcast_to(jnp.asarray(k_pos)[None], (B, Sk)),
                         causal=True, window=0, chunk=4)
    assert np.array_equal(np.asarray(o1), np.asarray(o2))


# --------------------------------------------------- ragged ring writes

@pytest.fixture(scope="module")
def lm():
    cfg = ARCHS["olmo-1b"].reduced()
    model = build_model(cfg)
    return model, model.init(jax.random.PRNGKey(7))


def _kv_positions(caches):
    """All KVCache.positions leaves of a decode state (i32, -1 sentinel)."""
    return [leaf for leaf in jax.tree.leaves(caches)
            if leaf.dtype == jnp.int32]


def test_ragged_extension_pad_rows_never_clobber_the_ring(lm):
    """A padded tail chunk near the ring's end: the pad's phantom positions
    wrap capacity and land on slots holding LIVE keys — the masked scatter
    must write the old contents back, bit for bit."""
    model, params = lm
    max_len = 16
    rng = np.random.RandomState(3)
    head = rng.randint(0, 256, size=(1, 14)).astype(np.int32)
    st = model.init_decode_state(1, max_len)
    _, st = model.extend(params, st, jnp.asarray(head))    # positions 0..13

    # 1 real token at offset 14, padded to 8: phantom positions 15..21 wrap
    # onto slots 15, 0..5 — six of those slots hold live keys
    toks = np.zeros((1, 8), np.int32)
    toks[0, 0] = 7
    lg_r, st_r = model.extend(params, st, jnp.asarray(toks),
                              lengths=jnp.asarray([1], np.int32))
    # reference: the same token padded by one, whose phantom (position 15)
    # lands on the ring's one empty slot.  Both run the chunked attention
    # path; a single token would decode through ``ring_attention``, whose
    # contractions sum in another order.
    lg_1, st_1 = model.extend(params, st, jnp.asarray([[7, 0]], np.int32),
                              lengths=jnp.asarray([1], np.int32))
    assert np.array_equal(np.asarray(lg_r), np.asarray(lg_1))
    lg_d, _ = model.extend(params, st, jnp.asarray([[7]], np.int32))
    np.testing.assert_allclose(np.asarray(lg_r), np.asarray(lg_d),
                               rtol=1e-5, atol=1e-5)
    assert np.asarray(st_r["pos"]).tolist() == [15]
    for got, ref in zip(jax.tree.leaves(st_r["caches"]),
                        jax.tree.leaves(st_1["caches"])):
        assert np.array_equal(np.asarray(got), np.asarray(ref))
    # the wrapped slots really were at stake: positions 0..5 survive (an
    # unmasked scatter would have stamped them 16..21), slot 14 took the
    # real token, slot 15 (phantom 15) stayed empty
    for leaf in _kv_positions(st_r["caches"]):
        for row in np.asarray(leaf).reshape(-1, max_len):
            assert (row[:6] == np.arange(6)).all()
            assert row[14] == 14 and row[15] == -1


def test_ragged_extension_zero_length_row_is_untouched(lm):
    """Length-0 rows (idle admission lanes) neither write KV nor advance
    their position."""
    model, params = lm
    st = model.init_decode_state(2, 16)
    toks = np.zeros((2, 4), np.int32)
    toks[0] = [5, 6, 7, 8]
    _, st2 = model.extend(params, st, jnp.asarray(toks),
                          lengths=jnp.asarray([4, 0], np.int32))
    assert np.asarray(st2["pos"]).tolist() == [4, 0]
    for leaf in _kv_positions(st2["caches"]):
        row1 = np.asarray(leaf)[..., 1, :] if leaf.ndim == 3 \
            else np.asarray(leaf)[1]
        assert (row1 == -1).all()


def test_extension_chunk_wider_than_ring_raises(lm):
    """Regression: a chunk wider than the KV ring would make in-chunk
    positions alias slots (nondeterministic scatter) — it must be rejected,
    ragged or not."""
    model, params = lm
    st = model.init_decode_state(1, 8)
    toks = jnp.zeros((1, 12), jnp.int32)
    with pytest.raises(ValueError, match="exceeds the KV ring capacity"):
        model.extend(params, st, toks)
    with pytest.raises(ValueError, match="exceeds the KV ring capacity"):
        model.extend(params, st, toks, lengths=jnp.asarray([5], jnp.int32))


# ------------------------------------------------ SWA chunked extension

@pytest.fixture(scope="module")
def swa():
    cfg = ARCHS["h2o-danube-3-4b"].reduced()          # window = 32 reduced
    model = build_model(cfg)
    return cfg, model, model.init(jax.random.PRNGKey(5))


def test_swa_chunked_extension_matches_whole_prompt_prefill(swa):
    """The retired NotImplementedError, pinned the other way: chunked SWA
    extension (each chunk attends against the carried pre-write ring, so
    recycling never evicts a live in-window key) must reproduce the
    one-shot whole-prompt prefill — logits and ring contents — even when
    the prompt wraps the window-capacity ring."""
    cfg, model, params = swa
    rng = np.random.RandomState(11)
    p = rng.randint(0, 256, size=40).astype(np.int32)  # > window = 32
    lg_ref, st_ref = model.prefill(params, {"tokens": jnp.asarray(p[None])},
                                   max_len=48)
    st = model.init_decode_state(1, 48)
    lg = None
    for o in range(0, 40, 8):
        lg, st = model.extend(params, st, jnp.asarray(p[None, o:o + 8]))
    assert np.argmax(np.asarray(lg)) == np.argmax(np.asarray(lg_ref))
    np.testing.assert_allclose(np.asarray(lg), np.asarray(lg_ref),
                               atol=1e-4, rtol=1e-4)
    assert np.asarray(st["pos"]).tolist() == [40]
    for got, ref in zip(jax.tree.leaves(st["caches"]),
                        jax.tree.leaves(st_ref["caches"])):
        got, ref = np.asarray(got), np.asarray(ref)
        if got.dtype == np.int32:                      # ring positions
            assert np.array_equal(got, ref)
        else:                                          # ring k/v contents
            np.testing.assert_allclose(got, ref, atol=1e-4, rtol=1e-4)


def test_swa_ragged_extension_rows_are_independent(swa):
    """A short row co-batched with a longer one must get bit-identical ring
    state and logits to the same row extended alone — pad columns are dead
    weight, not evictions."""
    cfg, model, params = swa
    rng = np.random.RandomState(12)
    toks = rng.randint(0, 256, size=(2, 8)).astype(np.int32)
    lens = jnp.asarray([8, 3], np.int32)
    st = model.init_decode_state(2, 48)
    lg, st2 = model.extend(params, st, jnp.asarray(toks), lengths=lens)
    st1 = model.init_decode_state(1, 48)
    lg1, st1 = model.extend(params, st1, jnp.asarray(toks[1:, :3]))
    assert np.asarray(st2["pos"]).tolist() == [8, 3]
    assert np.array_equal(np.asarray(lg[1]), np.asarray(lg1[0]))
    for got, ref in zip(jax.tree.leaves(st2["caches"]),
                        jax.tree.leaves(st1["caches"])):
        assert np.array_equal(np.asarray(got)[1:], np.asarray(ref))


def test_swa_ragged_stacked_prefill_builds_per_row_rings(swa):
    """The ragged SWA prefill ring build (per-row gather of each row's own
    last in-window keys): a short row stacked with a longer one must come
    out with the same ring a solo trimmed prefill builds — the old
    last-columns slice would have filled it with pads."""
    cfg, model, params = swa
    rng = np.random.RandomState(13)
    toks = rng.randint(0, 256, size=(2, 40)).astype(np.int32)
    toks[1, 9:] = 0                                    # row 1: 9 real + pads
    lens = jnp.asarray([40, 9], np.int32)
    lg, st = model.prefill(params, {"tokens": jnp.asarray(toks)},
                           max_len=48, lengths=lens)
    assert np.asarray(st["pos"]).tolist() == [40, 9]
    for b, L in ((0, 40), (1, 9)):
        lg1, st1 = model.prefill(
            params, {"tokens": jnp.asarray(toks[b:b + 1, :L])}, max_len=48)
        np.testing.assert_allclose(np.asarray(lg[b]), np.asarray(lg1[0]),
                                   atol=1e-4, rtol=1e-4)
        for got, ref in zip(jax.tree.leaves(st["caches"]),
                            jax.tree.leaves(st1["caches"])):
            got, ref = np.asarray(got)[b:b + 1], np.asarray(ref)
            if got.dtype == np.int32:
                assert np.array_equal(got, ref), (b, L)
            else:
                np.testing.assert_allclose(got, ref, atol=1e-4, rtol=1e-4)


def test_swa_extension_chunk_wider_than_window_still_raises(swa):
    """A chunk wider than the window-capacity ring still aliases slots
    within itself — it must stay rejected (serving clamps its chunk to the
    window, so this is unreachable through the engine)."""
    cfg, model, params = swa
    st = model.init_decode_state(1, 48)
    toks = jnp.zeros((1, 40), jnp.int32)               # 40 > window = 32
    with pytest.raises(ValueError, match="exceeds the KV ring capacity"):
        model.extend(params, st, toks)
    # single-token pooled decode steps keep working
    st = model.init_decode_state(2, 48)
    lg, _ = model.decode_step(params, st, jnp.zeros((2, 1), jnp.int32))
    assert np.isfinite(np.asarray(lg)).all()
