"""The Nemotron-H layer kinds at a tiny size, against plain f32 references:
one-mixer layers (Mamba-2 with grouped B/C, a sigmoid-routed MoE with a
shared expert and an expert share, NoPE attention) served through
``ServeEngine``, the expert share, pads and chunking, grouped SSD, and the
corrected router.

The served model is checked against the benchmark's plain reference of the
architecture (``bench/arch/nemotron_h.py``), which imports nothing of the
program; the grouped SSM mixer against a per-group loop written here.
"""

import dataclasses
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import ModelConfig
from repro.models import stats
from repro.models.layers import apply_dense
from repro.models.model_zoo import build_model
from repro.models.moe import apply_moe, init_moe, route
from repro.models.ssm import apply_ssm, init_ssm
from repro.serve import Request, ServeConfig, ServeEngine

BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "bench")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

from arch import nemotron_h as ref  # noqa: E402

PATTERN = ("ssm", "moe_only", "ssm", "moe_only", "ssm", "attn_only",
           "moe_only")

# Two whole periods, so the layers sit in scan groups; 16 experts routed,
# top 6, this "chip" holding experts 4-7; 8 heads of 8 in 4 groups.
TINY = ModelConfig(
    name="tiny-nemotron-h", family="hybrid", n_layers=14, d_model=64,
    n_heads=4, n_kv_heads=2, head_dim=16, d_ff=32, vocab_size=128,
    rope=False, norm_eps=1e-5, act="relu2", glu=False, n_experts=16,
    top_k=6, experts_held=4, expert_offset=4, moe_router="sigmoid",
    moe_scale=2.5, moe_shared_ff=48, ssm_state=8, ssm_heads=8,
    ssm_headdim=8, ssm_groups=4, ssm_chunk=8, block_pattern=PATTERN,
    tie_embeddings=False, dtype="float32", scan_unroll=1, attn_chunk=16)


def _m(cfg) -> dict:
    m = dataclasses.asdict(cfg)
    m.pop("dslot")
    return m


def _slow_ssm(params, key):
    """Slowly decaying SSM states (A in [1, 16], dt in [1e-3, 0.1]) and a
    random correction bias, so that state carried across chunks and steps
    matters and the bias moves the selection."""
    def walk(node, key):
        if isinstance(node, dict):
            if "A_log" in node:
                k1, k2 = jax.random.split(key)
                shape = node["A_log"].shape
                a = jax.random.uniform(k1, shape, minval=1.0, maxval=16.0)
                dt = jax.random.uniform(k2, shape, minval=1e-3, maxval=0.1)
                return {**node, "A_log": jnp.log(a),
                        "dt_bias": jnp.log(jnp.expm1(dt))}
            if "e_score_correction_bias" in node:
                b = node["e_score_correction_bias"]
                node = {**node, "e_score_correction_bias":
                        0.1 * jax.random.normal(key, b.shape)}
            keys = jax.random.split(key, len(node))
            return {k: walk(v, kk) for (k, v), kk in zip(node.items(), keys)}
        if isinstance(node, list):
            keys = jax.random.split(key, max(1, len(node)))
            return [walk(v, kk) for v, kk in zip(node, keys)]
        return node
    return walk(params, key)


def _flat(tree, prefix=""):
    out = {}
    for k, v in tree.items():
        name = f"{prefix}{k}"
        if isinstance(v, dict):
            out.update(_flat(v, name + "."))
        else:
            out[name] = v
    return out


def _reference_logits(params, cfg, tokens) -> np.ndarray:
    """The plain reference's full forward pass over ``tokens``, from the
    program's parameters laid out as the reference's leaves."""
    m = _m(cfg)
    dec = params["decoder"]
    period = len(dec["groups"])
    x = jnp.asarray(params["embed"]["embedding"])[jnp.asarray(tokens)]
    for l, kind in enumerate(ref.layer_kinds(m)):
        g, pos = divmod(l, period)
        leaves = {k: v[g] for k, v in _flat(dec["groups"][pos]).items()}
        x = ref.LAYERS[kind](x, leaves, m, None, 1.0, 8, False)
    glob = {"final_norm.scale": params["final_norm"]["scale"],
            "head.w": params["head"]["w"]}
    return np.asarray(ref.logits(glob, x, m, False))


@pytest.fixture(scope="module")
def served():
    model = build_model(TINY)
    params = _slow_ssm(model.init(jax.random.PRNGKey(0)),
                       jax.random.PRNGKey(1))
    return model, params


# ----------------------------------------------------------------- (a)

def test_serve_engine_matches_reference_forward(served):
    """Ragged chunked admission (two lanes of 8-token chunks), then pooled
    decode through ``ServeEngine``: every decode step's logits, and the
    first token of each request, match the reference's full forward pass
    over the prompt and the served tokens."""
    model, params = served
    eng = ServeEngine(model, params, ServeConfig(
        n_slots=2, max_len=64, prefill_chunk=8, chunks_per_step=2))
    seen = []
    decode = eng._decode

    def spy(p, st, t, npl):
        live = [(r, len(r.out)) if r is not None else None
                for r in eng.slot_req]
        lg, st2, aux = decode(p, st, t, npl)
        seen.append((live, np.asarray(lg)))
        return lg, st2, aux

    eng._decode = spy
    rng = np.random.RandomState(0)
    reqs = [Request(uid=i, prompt=rng.randint(0, 128, n).astype(np.int32),
                    max_new=6) for i, n in enumerate((13, 21))]
    for r in reqs:
        assert eng.try_add(r)
    eng.drain()
    for r in reqs:
        full = np.concatenate([r.prompt, np.asarray(r.out, np.int32)])
        want = _reference_logits(params, TINY, full)
        n = len(r.prompt)
        assert r.out[0] == int(np.argmax(want[n - 1]))
        rows = [(slot[1], lg[i]) for live, lg in seen
                for i, slot in enumerate(live)
                if slot is not None and slot[0] is r]
        assert [j for j, _ in rows] == list(range(len(r.out)))
        for j, got in rows:
            np.testing.assert_allclose(got, want[n + j], atol=2e-4,
                                       rtol=2e-4)
        routed, computed = r.result.expert_rows
        # 6 MoE layers x 4 held experts x C = T = 2 rows (2 slots), a step
        assert computed == 6 * 4 * 2 * len(r.out)
        assert 0 < routed <= computed


# ----------------------------------------------------------------- (b)

def test_expert_shares_sum_to_the_uncut_layer():
    """EP-8: the 8 shares' partial outputs, the shared expert counted once,
    add up to the uncut layer, and the uncut layer is the reference's."""
    full = dataclasses.replace(TINY, experts_held=16, expert_offset=0)
    p = _slow_ssm(init_moe(full, jax.random.PRNGKey(2)),
                  jax.random.PRNGKey(3))
    x = jax.random.normal(jax.random.PRNGKey(4), (2, 5, full.d_model))
    y_full, _ = apply_moe(p, x, full, dropless=True)
    shared = apply_dense(p["shared"]["down"], jnp.square(jax.nn.relu(
        apply_dense(p["shared"]["up"], x))))
    parts = shared
    for i in range(8):
        cfg = dataclasses.replace(full, experts_held=2, expert_offset=2 * i)
        share = {**p, "up": p["up"][2 * i:2 * i + 2],
                 "down": p["down"][2 * i:2 * i + 2]}
        y, _ = apply_moe(share, x, cfg, dropless=True)
        parts = parts + (y - shared)
    np.testing.assert_allclose(np.asarray(parts), np.asarray(y_full),
                               atol=1e-5, rtol=1e-5)
    leaves = {"norm.scale": jnp.ones((full.d_model,)),
              **{f"moe.{k}": v for k, v in _flat(p).items()}}
    # the reference normalizes its input: feed it a row already normalized
    xn = x / jnp.sqrt(jnp.mean(x * x, -1, keepdims=True) + full.norm_eps)
    want = ref.moe_layer(xn.reshape(-1, full.d_model), leaves, _m(full),
                         None, 1.0, 8, False) - xn.reshape(-1, full.d_model)
    got, _ = apply_moe(p, xn, full, dropless=True)
    np.testing.assert_allclose(np.asarray(got).reshape(want.shape),
                               np.asarray(want), atol=1e-4, rtol=1e-4)


# ----------------------------------------------------------------- (c)

@pytest.mark.parametrize("dropless", [True, False])
def test_pads_take_no_expert_rows(dropless):
    """Pads are routed nowhere: the rows routed count only real tokens, and
    what a pad holds changes no real token's output.  Serving (dropless)
    computes C = T rows per held expert."""
    cfg = dataclasses.replace(TINY, experts_held=16, expert_offset=0)
    p = init_moe(cfg, jax.random.PRNGKey(5))
    x = jax.random.normal(jax.random.PRNGKey(6), (2, 8, cfg.d_model))
    valid = jnp.arange(8)[None] < jnp.array([[3], [8]])
    junk = jnp.where(valid[..., None], x, 50.0)
    with stats.collect() as sink:
        y1, _ = apply_moe(p, x, cfg, q_valid=valid, dropless=dropless)
    y2, _ = apply_moe(p, junk, cfg, q_valid=valid, dropless=dropless)
    np.testing.assert_allclose(np.asarray(y1)[np.asarray(valid)],
                               np.asarray(y2)[np.asarray(valid)], atol=1e-5)
    assert float(sink["moe_rows_routed"][0]) == 11 * cfg.top_k
    if dropless:
        assert float(sink["moe_rows_computed"][0]) == 16 * 16


def test_prompt_logits_do_not_depend_on_chunking(served):
    """One prompt admitted whole, in ragged chunks of 8, or of 5 beside a
    longer lane: the same last-position logits and the same next decode
    step."""
    model, params = served
    prompt = np.random.RandomState(1).randint(0, 128, 19).astype(np.int32)
    other = np.random.RandomState(2).randint(0, 128, 30).astype(np.int32)
    outs = []
    for chunk in (19, 8, 5):
        st = model.init_decode_state(2, 64)
        done = 0
        while done < len(other):
            toks = np.zeros((2, chunk), np.int32)
            lens = np.zeros((2,), np.int32)
            for row, seq in enumerate((prompt, other)):
                piece = seq[done:done + chunk]
                toks[row, :len(piece)] = piece
                lens[row] = len(piece)
            lg, st = model.extend(params, st, jnp.asarray(toks),
                                  lengths=jnp.asarray(lens))
            if done < len(prompt) <= done + chunk:
                last = np.asarray(lg[0])
            done += chunk
        nxt, _ = model.decode_step(params, st, jnp.array([[7], [9]]))
        outs.append((last, np.asarray(nxt[0])))
    for last, nxt in outs[1:]:
        np.testing.assert_allclose(last, outs[0][0], atol=1e-4, rtol=1e-4)
        np.testing.assert_allclose(nxt, outs[0][1], atol=1e-4, rtol=1e-4)


# ----------------------------------------------------------------- (d)

def _ssm_per_group(p, x, cfg):
    """The Mamba-2 mixer written group by group in numpy: group g's B and C
    serve its own heads, and its own channels are normalized together."""
    f = lambda a: np.asarray(a, np.float64)  # noqa: E731
    P, H, G, N = cfg.ssm_headdim, cfg.ssm_heads, cfg.ssm_groups, cfg.ssm_state
    di, k, per = H * P, cfg.ssm_conv, H // G
    T = x.shape[0]
    zxbcdt = f(x) @ f(p["w_in"])
    z, xbc, dt = zxbcdt[:, :di], zxbcdt[:, di:2 * di + 2 * G * N], \
        zxbcdt[:, 2 * di + 2 * G * N:]
    xp = np.concatenate([np.zeros((k - 1, xbc.shape[1])), xbc])
    conv = sum(xp[j:j + T] * f(p["conv_w"])[j] for j in range(k)) \
        + f(p["conv_b"])
    xbc = conv / (1 + np.exp(-conv))
    xs, Bm, Cm = xbc[:, :di], xbc[:, di:di + G * N], xbc[:, di + G * N:]
    dt = np.log1p(np.exp(dt + f(p["dt_bias"])))
    A = -np.exp(f(p["A_log"]))
    y = np.zeros((T, di))
    for g in range(G):
        Bg, Cg = Bm[:, g * N:(g + 1) * N], Cm[:, g * N:(g + 1) * N]
        for h in range(g * per, (g + 1) * per):
            state = np.zeros((P, N))
            for t in range(T):
                xt = xs[t, h * P:(h + 1) * P]
                state = np.exp(dt[t, h] * A[h]) * state \
                    + dt[t, h] * np.outer(xt, Bg[t])
                y[t, h * P:(h + 1) * P] = state @ Cg[t] \
                    + f(p["D_skip"])[h] * xt
    gated = y * (z / (1 + np.exp(-z)))
    w = di // G
    for g in range(G):
        blk = gated[:, g * w:(g + 1) * w]
        gated[:, g * w:(g + 1) * w] = blk / np.sqrt(
            np.mean(blk * blk, -1, keepdims=True) + cfg.norm_eps)
    return (gated * f(p["norm_scale"])) @ f(p["w_out"])


@pytest.mark.parametrize("sequential", [False, True])
def test_grouped_ssm_matches_per_group_reference(sequential):
    """G = 8 groups of 2 heads, H P = 128 against ssm_expand * d_model = 64:
    chunked and sequential SSD equal the per-group loop."""
    cfg = dataclasses.replace(TINY, d_model=32, ssm_expand=2, ssm_heads=16,
                              ssm_headdim=8, ssm_groups=8, ssm_state=4,
                              ssm_chunk=8)
    p = _slow_ssm(init_ssm(cfg, jax.random.PRNGKey(7)),
                  jax.random.PRNGKey(8))
    p["norm_scale"] = 1 + 0.1 * jax.random.normal(jax.random.PRNGKey(9),
                                                  p["norm_scale"].shape)
    p["conv_b"] = 0.1 * jax.random.normal(jax.random.PRNGKey(10),
                                          p["conv_b"].shape)
    assert p["w_in"].shape == (32, 2 * 128 + 2 * 8 * 4 + 16)
    x = jax.random.normal(jax.random.PRNGKey(11), (1, 20, 32))
    y, _ = apply_ssm(p, x, cfg, sequential=sequential)
    np.testing.assert_allclose(np.asarray(y[0]), _ssm_per_group(p, x[0], cfg),
                               atol=1e-4, rtol=1e-4)


# ----------------------------------------------------------------- (e)

def test_correction_bias_moves_the_choice_not_the_weights():
    """The bias picks the experts; their weights are the plain sigmoid
    scores, renormalized over the chosen and times ``moe_scale``."""
    cfg = dataclasses.replace(TINY, n_experts=8, top_k=2, experts_held=8,
                              expert_offset=0)
    router = jnp.zeros((cfg.d_model, 8)).at[0].set(
        jnp.array([2.0, 1.5, 1.0, 0.5, 0.0, -0.5, -1.0, -1.5]))
    x = jnp.zeros((1, cfg.d_model)).at[0, 0].set(1.0)
    bias = jnp.zeros((8,)).at[6].set(3.0)
    p = {"router": router, "e_score_correction_bias": jnp.zeros((8,))}
    w0, idx0, _ = route(p, x, cfg)
    w1, idx1, _ = route({**p, "e_score_correction_bias": bias}, x, cfg)
    assert sorted(np.asarray(idx0[0]).tolist()) == [0, 1]
    assert sorted(np.asarray(idx1[0]).tolist()) == [0, 6]
    s = jax.nn.sigmoid(router[0])
    for w, idx in ((w0, idx0), (w1, idx1)):
        chosen = s[idx[0]]
        np.testing.assert_allclose(np.asarray(w[0]),
                                   np.asarray(chosen / chosen.sum() * 2.5),
                                   rtol=1e-6)


# ----------------------------------------------------------------- scopes

def test_decode_program_ops_carry_the_mixer_scopes(served):
    """The expert and SSM sub-layers name their ops inside the layer
    scan's body, ready for per-scope device time."""
    import re
    model, params = served
    eng = ServeEngine(model, params, ServeConfig(n_slots=2, max_len=32,
                                                 prefill_chunk=8))
    toks = jnp.zeros((eng.n_slots, 1), jnp.int32)
    text = eng._decode.lower(eng.params, eng.state, toks,
                             eng._budget_vector()).compile().as_text()
    scanned = [n for n in re.findall(r'op_name="([^"]*)"', text)
               if "/while/body/" in n]
    for path in ("/moe/router/", "/moe/experts/", "/moe/shared/",
                 "/ssm/conv/", "/ssm/scan/", "/ssm/out/", "/attn/kv_ring/"):
        assert any(path in n for n in scanned), path
