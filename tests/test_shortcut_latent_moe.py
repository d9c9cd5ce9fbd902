"""The shortcut-connected latent-attention, sparse-expert decoder
(``models/shortcut_latent_moe.py``) and what it forced: a layer of two
latent-attention sublayers and two dense feed-forwards with an expert
branch across them, a paged pool with more rows a token than the model
has layers, a softmax router over real and identity experts with a
selection bias and no groups, and identity experts that compute
nothing.

Everything is held against the benchmark's plain reference
(``benchmark/configs/longcat-flash-ep32.reference.py``, which imports
nothing of the program) at a tiny size with the published *structure*:
two layers (four cached sublayers), 16 real and 8 identity experts, 4 a
token, 2 heads, a rope slice of 8, both ranks below the hidden size,
both low-rank factors on.  float32 on the CPU, so the two sides differ
by the order of float32 additions only.
"""

import copy
import json
import math
import os
import shutil

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from mxnet_tpu import observability as obs
from mxnet_tpu import serving
from mxnet_tpu.models import latent_moe
from mxnet_tpu.models import shortcut_latent_moe as sm
from mxnet_tpu.parallel import moe

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CONFIG = os.path.join(ROOT, "benchmark", "configs", "longcat-flash-ep32.json")
REFERENCE = CONFIG[:-len(".json")] + ".reference.py"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
# the benchmark's configuration file at the tiny size: the published
# keys, the experts held (all 16 here), the deployment
TINY = {
    "family": "shortcut_latent_moe", "hidden_size": 32,
    "ffn_hidden_size": 64, "expert_ffn_hidden_size": 16, "num_layers": 2,
    "num_attention_heads": 2, "q_lora_rank": 12, "kv_lora_rank": 16,
    "qk_nope_head_dim": 8, "qk_rope_head_dim": 8, "v_head_dim": 8,
    "vocab_size": 50, "n_routed_experts": 16, "zero_expert_num": 8,
    "zero_expert_type": "identity", "moe_topk": 4,
    "routed_scaling_factor": 6, "mla_scale_q_lora": True,
    "mla_scale_kv_lora": True, "attention_method": "MLA",
    "rms_norm_eps": 1e-5, "rope_theta": 10000000, "n_positions": 64,
    "deployment": {"experts": {"published": 16, "held": 16, "first": 0},
                   "serve": {"dtype": "float32", "block_size": 4,
                             "num_blocks": 256}}}
# 0.3-wide weights and a 0.02-wide selection bias (softmax scores over
# 24 outputs are ~0.04): large enough that the experts, the rotary slice
# and the bias all move the logits
SCALE, BIAS_SCALE = 0.3, 0.02
REAL, WIDTH, TOP_K = 16, 24, 4


def held_config(first=0, count=16):
    cfg = copy.deepcopy(TINY)
    cfg["n_routed_experts"] = count
    cfg["deployment"]["experts"].update(held=count, first=first)
    return cfg


@pytest.fixture(scope="module")
def family():
    from benchmark.spec import load_module

    return load_module(os.path.join(
        ROOT, "benchmark", "models", "shortcut_latent_moe.py"),
        "family_shortcut")


@pytest.fixture(scope="module")
def reference():
    from benchmark.spec import load_module

    return load_module(REFERENCE, "reference_longcat")


@pytest.fixture(scope="module")
def model(family):
    cfg = family.program_config(TINY)
    return cfg, sm.init_params(cfg, 0, jnp.float32, SCALE, BIAS_SCALE)


def _tokens(n, seed=0):
    return np.random.RandomState(seed).randint(
        0, TINY["vocab_size"], n).astype(np.int32)


def _reference_logits(reference, cfg_file, params, toks, mode="float32"):
    """The reference's logits ``[T, V]`` of one sequence, compiled (run
    eagerly its loops over heads, rows and experts take seconds)."""
    return np.asarray(jax.jit(lambda p, t: reference.logits(
        cfg_file, p, t, mode))(params, toks[None]))[0]


def _counter(name, model):
    for line in obs.REGISTRY.render().splitlines():
        if line.startswith('%s{model="%s"}' % (name, model)):
            return float(line.rsplit(" ", 1)[1])
    return 0.0


# ----------------------------------------------------------------------
# (a) prefill, then decode through the sublayers' pools, against the
# reference's full forward


@pytest.mark.parametrize("every_row", [None, False, True],
                         ids=["by_rule", "grouped", "every_row"])
def test_prefill_then_decode_through_the_pool_is_the_reference(
        model, reference, monkeypatch, every_row):
    """Logits of a 5-token prefill and of 15 decode steps through
    ``LMBackend`` and its pool of four sublayers' rows against the
    reference's one forward over all 20 tokens.  Tolerance 1e-4 on
    logits of size ~5: both sides are float32 on the CPU and differ in
    the order of their additions; a sublayer reading the other's pool, a
    dropped factor on a low-rank path or one identity choice missed
    moves them by 1e-2 or more.  The pool's leading axis is twice the
    layers."""
    cfg, params = model
    if every_row is not None:
        monkeypatch.setattr(moe, "few_rows_hit_most",
                            lambda *sizes: every_row)
    be = serving.LMBackend(params, definition=sm.lm_definition(
        cfg, jnp.float32), block_size=4, num_blocks=32, model="tiny_sc_a")
    assert be.cache.v_pages is None
    assert be.cache.k_pages.shape == (2 * cfg["num_layers"], 32, 4, 128)
    assert 'kv_cache_layers{model="tiny_sc_a"} 4' in obs.REGISTRY.render()
    assert 'kv_cache_row_bytes{model="tiny_sc_a"} 512' \
        in obs.REGISTRY.render()
    toks = _tokens(20)
    want = _reference_logits(reference, TINY, params, toks)
    assert np.abs(want).max() > 0.5
    padded = np.zeros(16, np.int32)
    padded[:5] = toks[:5]
    be.cache.allocate("s", 20)
    logits, k, v, _ = be.prefill(padded, 5)
    assert v is None and k.shape == (4, 16, 128)
    be.cache.write_prefill("s", k, v, 5)
    np.testing.assert_allclose(logits, want[4], atol=1e-4, rtol=0)
    for t in range(5, 20):
        table = be.cache.block_table("s", be.max_blocks_per_seq)[None]
        logits, k, v, _ = be.decode([toks[t]], [t], table, [t + 1])
        np.testing.assert_allclose(logits[0], want[t], atol=1e-4, rtol=0)


def test_full_forward_is_the_reference_on_a_share(family, reference):
    """The same with a share of the real experts held (ids 4-7 of 16):
    the reference leaves out what the absent twelve would add, as the
    program does, and both compute the identity experts' term whole."""
    share = held_config(first=4, count=4)
    cfg = family.program_config(share)
    params = sm.init_params(cfg, 1, jnp.float32, SCALE, BIAS_SCALE)
    toks = _tokens(12, seed=1)
    got = np.asarray(jax.jit(lambda p, t: sm.full_logits(p, t, cfg))(
        params, toks[None]))[0]
    want = _reference_logits(reference, share, params, toks)
    np.testing.assert_allclose(got, want, atol=1e-4, rtol=0)


# ----------------------------------------------------------------------
# (b) the absorbed decode is the expanded prefill, with both factors


@pytest.mark.parametrize("factors", [(True, True), (True, False),
                                     (False, True), (False, False)],
                         ids=["q_kv", "q", "kv", "none"])
def test_absorbed_decode_equals_expanded_prefill(family, model, factors):
    """One sublayer's attention at position 9: the absorbed form over
    the paged rows of positions 0-8 against the last row of the expanded
    form over all ten, with each of the two low-rank factors on and off
    (the cache row holds the scaled latent, so both forms see it).
    1e-5: the same float32 products summed in another order.  A factor
    moves the result."""
    _, params = model
    on = family.program_config(dict(
        TINY, mla_scale_q_lora=factors[0], mla_scale_kv_lora=factors[1]))
    assert on["q_lora_scale"] == (math.sqrt(32 / 12) if factors[0] else None)
    assert on["kv_lora_scale"] == (math.sqrt(32 / 16) if factors[1]
                                   else None)
    x = jax.random.normal(jax.random.PRNGKey(3), (10, on["hidden_size"]))
    positions = jnp.arange(10, dtype=jnp.int32)
    expanded, rows = latent_moe._attention_prefill(params, "l1_s1_", x,
                                                   positions, on)
    pages = jnp.zeros((8, 4, rows.shape[-1])).at[
        jnp.array([5, 5, 5, 5, 2, 2, 2, 2, 7]),
        jnp.array([0, 1, 2, 3, 0, 1, 2, 3, 0])].set(rows[:9])
    absorbed, row = latent_moe._attention_decode(
        params, "l1_s1_", x[9:], positions[9:], pages,
        jnp.array([[5, 2, 7, 0]], jnp.int32), jnp.array([10], jnp.int32),
        on)
    np.testing.assert_allclose(row[0], rows[9], atol=1e-6)
    np.testing.assert_allclose(absorbed[0], expanded[9], atol=1e-5, rtol=0)
    if any(factors):
        off = family.program_config(dict(TINY, mla_scale_q_lora=False,
                                         mla_scale_kv_lora=False))
        plain, _ = latent_moe._attention_prefill(params, "l1_s1_", x,
                                                 positions, off)
        assert np.abs(np.asarray(plain - expanded)).max() > 1e-3


def test_plain_rotary_is_a_stated_case():
    """A configuration without ``rope_scaling`` (absent or null) turns
    by ``1 / theta^(2i / rope)`` with cos and sin unscaled, and its
    softmax scale is ``(nope + rope)^-0.5``; the latent family's own
    ``lm_config`` takes such a file."""
    cfg = {"qk_rope_head_dim": 64, "qk_nope_head_dim": 128,
           "rope_theta": 10000000}
    for plain in (cfg, dict(cfg, rope_scaling=None)):
        inv, scale = latent_moe.yarn_inv_freq(plain)
        assert scale == 1.0 and inv.dtype == np.float32
        np.testing.assert_allclose(inv, 1e7 ** (-np.arange(32) / 32.0),
                                   rtol=1e-6)
        assert latent_moe.softmax_scale(plain) == 192 ** -0.5
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "dots-vlm1-ep16.json")) as f:
        dots = json.load(f)
    for published in (dict(dots, rope_scaling=None),
                      {k: v for k, v in dots.items() if k != "rope_scaling"}):
        assert latent_moe.lm_config(published, 4096)["rope_scaling"] is None
    assert latent_moe.lm_config(dots, 4096)["rope_scaling"]["factor"] == 40


# ----------------------------------------------------------------------
# (c) the router


def _route(logits, bias):
    return moe.route_softmax_topk(
        jnp.asarray(logits), top_k=TOP_K, normalize=False,
        bias=jnp.asarray(bias), scale=6)


def test_router_is_the_reference_exactly(reference):
    """Chosen sets and gates over 64 tokens: the same experts, the same
    float32 gates to the bit (both sides compute one softmax, one sum
    and one product); six times the unbiased score, not renormalised."""
    rng = np.random.RandomState(5)
    logits = rng.randn(64, WIDTH).astype(np.float32)
    bias = (0.02 * rng.randn(WIDTH)).astype(np.float32)
    chosen, gates = _route(logits, bias)
    ref_chosen, ref_gates = reference.route(TINY, jnp.asarray(logits),
                                            jnp.asarray(bias))
    np.testing.assert_array_equal(np.asarray(chosen), np.asarray(ref_chosen))
    np.testing.assert_array_equal(np.asarray(gates), np.asarray(ref_gates))
    s = np.exp(logits.astype(np.float64))
    s /= s.sum(-1, keepdims=True)
    np.testing.assert_allclose(
        np.asarray(gates), 6 * np.take_along_axis(s, np.asarray(chosen), 1),
        rtol=1e-5)
    sums = np.asarray(gates).sum(-1)
    assert sums.max() < 6 and sums.std() > 0.1        # not renormalised
    assert (np.asarray(chosen) >= REAL).any()         # identity ids too


def test_selection_bias_changes_the_choice_and_not_the_gate(reference):
    """Expert 5 scores below expert 4 and is chosen in its place once
    its bias lifts it; the gates are the scores without the bias.  The
    router without a bias is the Qwen router it extends."""
    logits = np.full((1, WIDTH), -4.0, np.float32)
    logits[0, [0, 1, 20, 4]] = [2.0, 1.5, 1.0, 0.5]
    logits[0, 5] = 0.4
    chosen, gates = _route(logits, np.zeros(WIDTH, np.float32))
    assert sorted(np.asarray(chosen)[0]) == [0, 1, 4, 20]
    plain = moe.route_softmax_topk(jnp.asarray(logits), top_k=TOP_K,
                                   normalize=False)
    np.testing.assert_array_equal(np.asarray(plain[0]), np.asarray(chosen))
    np.testing.assert_allclose(6 * np.asarray(plain[1]), np.asarray(gates),
                               rtol=1e-6)
    bias = np.zeros(WIDTH, np.float32)
    bias[5] = 0.05
    chosen_b, gates_b = _route(logits, bias)
    assert sorted(np.asarray(chosen_b)[0]) == [0, 1, 5, 20]
    s = np.exp(logits[0].astype(np.float64))
    s /= s.sum()
    order = np.argsort(np.asarray(chosen_b)[0])
    np.testing.assert_allclose(np.asarray(gates_b)[0][order],
                               6 * s[[0, 1, 5, 20]], rtol=1e-5)
    ref_chosen, ref_gates = reference.route(TINY, jnp.asarray(logits),
                                            jnp.asarray(bias))
    np.testing.assert_array_equal(np.asarray(chosen_b),
                                  np.asarray(ref_chosen))
    np.testing.assert_array_equal(np.asarray(gates_b), np.asarray(ref_gates))


# ----------------------------------------------------------------------
# (d) identity experts, (e) the shares add up, (f) no token is dropped


def _weights(params, prefix="l1_"):
    return {k[len(prefix):]: v for k, v in params.items()
            if k.startswith(prefix)}


def _share_part(w, h, chosen, gates, first, count, every_row=False,
                valid=None):
    """One share's routed part: its own slice of the real experts."""
    part, counts = moe.dropless_experts(
        h, chosen, gates, w["experts_gate_weight"][first:first + count],
        w["experts_up_weight"][first:first + count],
        w["experts_down_weight"][first:first + count], (first, count),
        valid=valid, every_row=every_row)
    return np.asarray(part), np.asarray(counts)


FORMS = pytest.mark.parametrize("every_row", [False, True],
                                ids=["grouped", "every_row"])


@FORMS
def test_a_token_of_identity_choices_alone_gets_its_gates_times_h(
        model, every_row):
    """Token 0 chooses four identity experts: the held experts compute
    nothing for it (no local pair, no expert hit, a zero routed part)
    and the layer gives ``(sum of its gates) * h``; token 1 chooses two
    of each kind."""
    cfg, params = model
    w = _weights(params)
    h = jax.random.normal(jax.random.PRNGKey(6), (2, cfg["hidden_size"]))
    chosen = jnp.array([[16, 23, 19, 20], [3, 17, 0, 22]], jnp.int32)
    gates = jnp.array([[0.5, 0.25, 1.0, 0.125], [0.5, 0.25, 1.0, 2.0]])
    part, counts = _share_part(w, h, chosen, gates, 0, 16, every_row)
    assert list(counts) == [8, 2, 2, 1, 0]
    assert not part[0].any() and part[1].any()
    same, zero = moe.identity_experts(h, chosen, gates, REAL)
    assert int(zero) == 6
    np.testing.assert_allclose(np.asarray(same)[0],
                               1.875 * np.asarray(h)[0], rtol=1e-6)
    np.testing.assert_allclose(np.asarray(same)[1],
                               2.25 * np.asarray(h)[1], rtol=1e-6)
    # a bucket's pad rows choose nothing, real or identity
    valid = jnp.array([False, True])
    same_v, zero_v = moe.identity_experts(h, chosen, gates, REAL, valid)
    assert int(zero_v) == 2 and not np.asarray(same_v)[0].any()
    # and the whole branch, with the router told to choose so
    alone = moe.route_softmax_topk
    try:
        moe.route_softmax_topk = lambda *a, **k: (chosen, gates)
        m, six = sm._experts(params, "l1_", h, cfg)
    finally:
        moe.route_softmax_topk = alone
    assert list(np.asarray(six)) == [8, 2, 2, 1, 0, 6]
    np.testing.assert_allclose(np.asarray(m)[0], 1.875 * np.asarray(h)[0],
                               rtol=1e-6)


def _reference_branch(reference, cfg_file, w, h):
    return np.asarray(reference._expert_layer(
        cfg_file, w, h, reference._Math("float32")))


@FORMS
@pytest.mark.parametrize("shares", [1, 2, 4, 8, 16])
def test_the_shares_add_up_to_the_uncut_layer(model, reference, shares,
                                              every_row):
    """Every split of the 16 real experts into equal shares: the
    shares' routed parts, plus the identity experts' term counted once,
    are the uncut reference's branch; the shares' local pairs and the
    identity pairs are all the pairs."""
    cfg, params = model
    w = _weights(params)
    h = jax.random.normal(jax.random.PRNGKey(7), (24, cfg["hidden_size"]))
    chosen, gates = sm._route(params, "l1_", h, cfg)
    count = 16 // shares
    parts = [_share_part(w, h, chosen, gates, i * count, count, every_row)
             for i in range(shares)]
    same, zero = moe.identity_experts(h, chosen, gates, REAL)
    assert 0 < int(zero) < 24 * TOP_K
    total = sum(p for p, _ in parts) + np.asarray(same)
    want = _reference_branch(reference, TINY, w, h)
    np.testing.assert_allclose(total, want, atol=2e-5, rtol=0)
    assert sum(c[1] for _, c in parts) + int(zero) == 24 * TOP_K
    assert all(c[0] == 24 * TOP_K for _, c in parts)
    # and a share alone is the reference told of the same share
    cut = held_config(first=count, count=count) if shares > 1 else TINY
    first = cut["deployment"]["experts"]["first"]
    w_cut = dict(w, **{k: w[k][first:first + count] for k in (
        "experts_gate_weight", "experts_up_weight", "experts_down_weight")})
    np.testing.assert_allclose(
        parts[min(1, shares - 1)][0] + np.asarray(same),
        _reference_branch(reference, cut, w_cut, h), atol=2e-5, rtol=0)


@FORMS
@pytest.mark.parametrize("tokens", [1, 24, 200])
def test_no_token_is_dropped_at_any_skew(model, reference, tokens,
                                         every_row, monkeypatch):
    """All tokens to one real expert (their other choices two more and
    one identity expert): every pair is computed, at any number of
    tokens, whole or in runs of 64 sorted pairs, with every run beyond
    the first counted."""
    cfg, params = model
    w = _weights(params)
    h = jax.random.normal(jax.random.PRNGKey(8), (tokens, cfg["hidden_size"]))
    chosen = jnp.tile(jnp.array([[3, 0, 21, 2]], jnp.int32), (tokens, 1))
    gates = jnp.tile(jnp.array([[1.0, 0.5, 0.25, 0.75]]), (tokens, 1))
    want = sum(
        g * np.asarray(reference._swiglu(
            reference._Math("float32"), h, w["experts_gate_weight"][e],
            w["experts_up_weight"][e], w["experts_down_weight"][e]))
        for e, g in zip([3, 0, 2], [1.0, 0.5, 0.75]))
    row_bytes = h.shape[1] * h.dtype.itemsize
    for pairs in (None, 64):
        if pairs:       # the rows of 64 pairs: a longer call runs in runs
            monkeypatch.setattr(moe, "GROUPED_ROW_BYTES", pairs * row_bytes)
        kept = moe.grouped_kept_rows(4 * tokens, 16, 16, row_bytes)
        assert kept == min(4 * tokens, pairs or 4 * tokens)
        extra = 0 if every_row else -(-3 * tokens // kept) - 1
        assert extra == (0 if every_row or not pairs else
                         {1: 0, 24: 1, 200: 9}[tokens])
        part, counts = _share_part(w, h, chosen, gates, 0, 16, every_row)
        assert list(counts) == [4 * tokens, 3 * tokens, 3, 1, extra]
        np.testing.assert_allclose(part, want, atol=2e-5, rtol=0)
    valid = jnp.arange(tokens) < max(1, tokens // 2)
    part_v, counts_v = _share_part(w, h, chosen, gates, 0, 16, every_row,
                                   valid)
    assert int(counts_v[1]) == 3 * max(1, tokens // 2)
    assert not part_v[max(1, tokens // 2):].any()


def test_which_calls_compute_every_row():
    """The cell's decode step (64 rows, 12 of 768: 8 real of 512)
    computes every held expert over every row; a prefill keeps the
    grouped form, a run of it the held experts' rows twice over: no
    bucket of the expert cells comes near ``GROUPED_ROW_BYTES``, which
    bounded 32,768 pairs' rows before the cut."""
    assert moe.few_rows_hit_most(64, 12, 768)
    assert moe.few_rows_hit_most(64, 8, 512)
    assert not moe.few_rows_hit_most(16, 12, 768)
    assert not moe.few_rows_hit_most(512, 12, 768)
    # the cell's buckets (bfloat16 rows of 6144): 16 of 768 outputs
    # held, a 24th of the pairs kept; all held would bound 32,768 rows
    limit = moe.GROUPED_ROW_BYTES // (6144 * 2)
    assert 2048 * 12 <= limit < 3072 * 12
    assert moe.grouped_kept_rows(6144 * 12, 16, 768, 6144 * 2) == 3072
    assert moe.grouped_kept_rows(6144 * 12, 768, 768, 6144 * 2) == limit
    # the largest bucket of the other two expert cells
    assert moe.grouped_kept_rows(3328 * 8, 16, 256, 7168 * 2) == 3328
    assert moe.grouped_kept_rows(4096 * 10, 128, 512, 2048 * 2) == 20480


# ----------------------------------------------------------------------
# (g) the shortcut: where the branch leaves and where it lands


def test_the_branch_reads_the_first_sublayer_and_lands_at_the_end(
        model, reference, monkeypatch):
    """``m = MoE(N(a1))`` is computed from the first attention's output
    and added after the second feed-forward: perturbing the second
    sublayer's attention leaves what the branch read and gave unchanged
    to the bit and moves ``y``; ``y`` less the layer without its branch
    is ``m``; both are the reference's."""
    cfg, params = model
    x = jax.random.normal(jax.random.PRNGKey(4), (10, cfg["hidden_size"]))
    positions = jnp.arange(10, dtype=jnp.int32)
    seen = []
    branch = sm._experts

    def watched(params, p, h, cfg, valid=None):
        m, counts = branch(params, p, h, cfg, valid)
        seen.append((np.asarray(h), np.asarray(m)))
        return m, counts

    def run(p):
        return np.asarray(sm._layer(
            p, 1, x, lambda prefix, _, u: latent_moe._attention_prefill(
                p, prefix, u, positions, cfg), cfg)[0])

    monkeypatch.setattr(sm, "_experts", watched)
    y = run(params)
    moved = dict(params, l1_s1_o_weight=params["l1_s1_o_weight"] * 1.5)
    y_moved = run(moved)
    (h, m), (h_moved, m_moved) = seen
    np.testing.assert_array_equal(h, h_moved)
    np.testing.assert_array_equal(m, m_moved)
    assert np.abs(y - y_moved).max() > 1e-2
    first = dict(params, l1_s0_o_weight=params["l1_s0_o_weight"] * 1.5)
    run(first)
    assert np.abs(seen[2][0] - h).max() > 1e-3     # it does read sublayer 0
    monkeypatch.setattr(sm, "_experts", lambda params, p, h, cfg,
                        valid=None: (jnp.zeros_like(h), jnp.zeros(5)))
    np.testing.assert_allclose(y - run(params), m, atol=1e-5, rtol=0)
    ar = reference._Math("float32")
    want_y, want_m = reference.layer(TINY, params, 1, x, ar,
                                     reference.rotary(TINY, 10))
    np.testing.assert_allclose(m, np.asarray(want_m), atol=2e-5, rtol=0)
    np.testing.assert_allclose(y, np.asarray(want_y), atol=1e-4, rtol=0)


# ----------------------------------------------------------------------
# the counters, the served path


def test_served_through_the_scheduler_and_counted(model):
    """The normal path: ``GenerationScheduler`` -> ``LMBackend`` -> the
    sublayers' pools, greedy tokens equal to a full forward's, nothing
    compiled after warm-up, and the expert counters booked from the
    counts that rode back with the logits, the identity choices among
    them."""
    cfg, params = model
    name = "tiny_sc_served"
    be = serving.LMBackend(params, definition=sm.lm_definition(
        cfg, jnp.float32), block_size=4, num_blocks=64, model=name)
    sched = serving.GenerationScheduler(name="shortcut")
    counted = moe.EXPERT_COUNTS + (moe.ZERO_COUNT,)
    try:
        sched.register(name, be, decode_buckets=[2, 4],
                       prefill_buckets=[8, 16])
        sched.warmup(name)
        cold = _counter("generation_compiles_total", name)
        before = {n: _counter(n, name) for n in counted}
        prompts = [_tokens(6, 11), _tokens(11, 12), _tokens(3, 13)]
        reqs = [sched.submit(name, p, max_new_tokens=6) for p in prompts]
        outs = [r.result(timeout=120) for r in reqs]
        forward = jax.jit(lambda t: sm.full_logits(params, t, cfg)[0])
        for prompt, out in zip(prompts, outs):
            seq = np.zeros(24, np.int32)     # causal: the pad is unseen
            seq[:len(prompt)], n = prompt, len(prompt)
            for tok in out:
                want = np.asarray(forward(seq[None]))[n - 1]
                assert want[tok] >= want.max() - 1e-4
                seq[n], n = tok, n + 1
        assert _counter("generation_compiles_total", name) == cold
        after = {n: _counter(n, name) - before[n] for n in counted}
        layer_calls = after["moe_layer_steps_total"]
        assert layer_calls >= 2 * (3 + 5)        # 3 prefills, >= 5 steps
        # all 16 real experts are held: a pair is local or identity
        assert after["moe_assignments_total"] >= TOP_K * 2 * (20 + 15)
        assert after["moe_assignments_total"] \
            == after["moe_local_assignments_total"] \
            + after["moe_zero_assignments_total"]
        assert 0.1 < after["moe_zero_assignments_total"] \
            / after["moe_assignments_total"] < 0.6      # 8 of 24 outputs
        assert 0 < after["moe_local_experts_hit_total"] <= 16 * layer_calls
    finally:
        sched.close()


# ----------------------------------------------------------------------
# the configuration, its counts, the benchmark's arithmetic and readers


def _published():
    with open(CONFIG) as f:
        return json.load(f)


def test_configuration_keeps_the_published_widths():
    """Every number of the catalog's row is in the file under its key,
    but for the keys ``reduced`` names; no width is among them."""
    cfg = _published()
    if not os.path.exists(CATALOG):
        pytest.skip("no catalog here")
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "LongCat-Flash-Omni")
    assert cfg["source"] == row["source_url"]
    for key, value in row["config"].items():
        if key in cfg["reduced"]:
            assert cfg[key] != value and cfg["published"][key] == value
            assert key in cfg["reduced_why"]
        else:
            assert cfg[key] == value, key
    assert sorted(cfg["reduced"]) == ["n_routed_experts", "num_layers",
                                      "vocab_size"]
    share = cfg["deployment"]["experts"]
    assert share == {"published": 512, "held": cfg["n_routed_experts"],
                     "first": 0}
    assert cfg["deployment"]["chips_sharing_a_layer"] * share["held"] == 512
    assert cfg["vocab_size"] * cfg["deployment"]["vocab_shards"] == 131072
    assert cfg["num_layers"] >= 4 and cfg["n_routed_experts"] >= 8
    assert "rope_scaling" not in cfg
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        entry = next(c for c in json.load(f)["configs"]
                     if c["name"] == "longcat-flash-ep32")
    assert entry["reduced"] == cfg["reduced"]
    assert entry["source"] == cfg["source"]


def test_parameter_count_of_the_cut_and_of_the_published_model(family):
    """ISSUE 32's arithmetic: a latent sublayer 90,572,800, a dense
    feed-forward 226,492,416, router and bias 4,719,360, four norms
    24,576: 638.9M a layer outside its experts; 16 held experts of
    37,748,736; embedding and head 201.3M: 5,172,749,312 parameters,
    10.35 GB in bfloat16.  The published model, 28 layers of 512
    experts and the whole vocabulary: 560.66B."""
    doc = _published()
    count = {k: int(np.prod(s)) for k, s in family.weight_shapes(doc).items()}

    def total(prefix, names=None):
        return sum(v for k, v in count.items() if k.startswith(prefix)
                   and (names is None or k[len(prefix):] in names))

    sublayer = total("l0_s0_") - total("l0_s0_ffn_") - count[
        "l0_s0_attn_norm_gamma"]
    assert sublayer == 90572800 == total("l3_s1_") - total("l3_s1_ffn_") \
        - count["l3_s1_attn_norm_gamma"]
    dense = total("l0_s0_ffn_", ("gate_weight", "up_weight", "down_weight"))
    assert dense == 226492416
    assert count["l0_router_weight"] + count["l0_router_bias"] == 4719360
    norms = sum(v for k, v in count.items() if k.startswith("l0_")
                and k.endswith(("attn_norm_gamma", "ffn_norm_gamma")))
    assert norms == 24576
    outside = 2 * sublayer + 2 * dense + 4719360 + norms
    assert outside == 638874368
    expert = total("l0_experts_") // 16
    assert expert == 37748736 and total("l0_") == outside + 16 * expert
    assert count["embed_weight"] + count["pred_weight"] == 201326592
    assert sum(count.values()) == 5172749312
    published = dict(doc, **{k: doc["published"][k] for k in doc["reduced"]})
    published["deployment"] = dict(doc["deployment"], experts={
        "published": 512, "held": 512, "first": 0})
    whole = sum(int(np.prod(s))
                for s in family.weight_shapes(published).values())
    assert whole == 28 * (outside + 512 * expert) + 2 * 131072 * 6144 + 6144
    assert abs(whole - 560.66e9) < 0.01e9
    # a token's pool row: 8 sublayers x 640 values x 2 bytes
    definition = sm.lm_definition(family.program_config(doc))
    assert definition.cache_layers == 8
    assert definition.cache_layers * definition.cache_row.bytes == 10240


def test_cost_arithmetic():
    from benchmark import flops
    from benchmark import latent_moe_costs as costs

    # the shared cost functions read an expert's width by dots' key,
    # which the configuration repeats
    cfg = costs.configuration("longcat-flash-ep32")
    assert cfg["moe_intermediate_size"] == cfg["expert_ffn_hidden_size"]
    ops, moved = costs.routed_experts_cost(cfg, experts_hit=10,
                                           local_assignments=16)
    assert ops == 16 * 2 * 3 * 6144 * 2048 == 16 * 75497472
    assert moved == 10 * 75497472 + 16 * (2 * 6144 + 3 * 2048) * 2
    # 64 rows at 2,900 cached tokens each, one sublayer
    ops, moved = costs.latent_decode_cost(cfg, context_tokens=185600,
                                          rows=64)
    assert ops == 2 * 185600 * 64 * (576 + 512)
    assert moved == (185600 * 576 + 64 * 64 * (576 + 512)) * 2
    peaks = {"flops_per_s": {"bfloat16": 197e12}, "hbm_bytes_per_s": 819e9}
    # 64 heads on a 576-wide row: 121 operations a byte, under the
    # chip's 240: the walk is bound by its reads (dots' 128 heads: 242)
    assert flops.roofline_seconds(ops, moved, peaks)[1] == "memory"
    for tokens, bound in ((1, "memory"), (200, "memory"), (400, "compute")):
        o, b = costs.routed_experts_cost(cfg, 1, tokens)
        assert flops.roofline_seconds(o, b, peaks)[1] == bound


WINDOW_NS = 200000000       # one prefill and one decode step fit in 0.2 s


def _trace(events):
    return {"window_ns": [0, WINDOW_NS], "devices": {"0": events},
            "host": []}


def _recorded_events():
    """(name, nanoseconds) of one prefill's and one decode step's
    operations as the chip's trace names them (cut from a traced run of
    the cell: the file says which)."""
    with open(os.path.join(ROOT, "benchmark", "data",
                           "longcat_trace_names.json")) as f:
        return [(e["name"], e["ns"]) for e in json.load(f)["events"]]


def test_readers_of_the_new_metrics(capsys):
    """On names recorded from the chip's trace: the shares count what
    their patterns name, the rooflines come out under 100% and say which
    peak bounds them, and every reader returns nothing where there is
    nothing to read (a program without the counters, as the parent is; a
    run without a trace)."""
    from benchmark.spec import Spec

    spec = Spec(ROOT)
    peaks = spec.peaks("TPU v5 lite")

    def read(metric, ctx):
        doc = spec.metric_file(metric)
        return spec.reader(doc["reader"])(ctx, doc.get("params", {}))

    at, events = 0, []
    for name, ns in _recorded_events():
        events.append([name, at, ns])
        at += ns
    assert at < WINDOW_NS

    def took(pattern):
        import re

        return sum(e[2] for e in events if re.search(pattern, e[0]))

    walks = sum(e[0].startswith("%latent_decode_attention") for e in events)
    assert walks == 8                       # one decode step: 8 sublayers
    flash = took(r"^%latent_prefill_attention")
    grouped = took(r"^%ragged-dot(?!-metadata)")
    batched = took(r"\[16,64,2048\]")
    assert flash and grouped and batched
    counters = {"moe_layer_steps_total": 80.0,
                "moe_local_experts_hit_total": 800.0,
                "moe_local_assignments_total": 1280.0,
                "moe_assignments_total": 61440.0,
                "moe_zero_assignments_total": 20480.0,
                "generation_decode_steps_total": 10.0,
                "generation_decode_context_tokens_total": 10 * 185600.0,
                "generation_tokens_total": 640.0}
    ctx = {"trace": _trace(events), "peaks": peaks,
           "compiles_in_window": counters}
    assert read("moe_expert_share.longcat", ctx) == pytest.approx(
        100.0 * (took(r"^%ragged-dot") + batched) / WINDOW_NS)
    assert read("mla_prefill_share.longcat", ctx) == pytest.approx(
        100.0 * flash / WINDOW_NS)
    assert read("paged_decode_attn_share.serve", ctx) == pytest.approx(
        100.0 * took(r"^%latent_decode_") / WINDOW_NS)
    # the traced kernels are 8 of the window's 80 layer calls' (3 each)
    kernels = sum(1 for e in events if e[0].startswith("%ragged-dot")
                  and "metadata" not in e[0].split(" = ")[0]) \
        + sum(1 for e in events if "[16,64,2048]" in e[0])
    share = kernels / 3.0 / 80
    least = (share * 800 * 75497472
             + share * 1280 * (2 * 6144 + 3 * 2048) * 2) / 819e9
    got = read("moe_expert_roofline.longcat", ctx)
    assert got == pytest.approx(100 * least / ((grouped + batched) * 1e-9))
    # 8 (sublayer, step) pairs at 185,600 cached rows each
    least = 8 * (185600 * 576 + 64 * 64 * 1088) * 2 / 819e9
    walk = read("mla_paged_decode_roofline.longcat", ctx)
    assert walk == pytest.approx(
        100 * least / (took(r"^%latent_decode_") * 1e-9))
    assert 0 < walk < 100
    out = capsys.readouterr().out
    assert "expert roofline: bound by memory" in out
    assert "latent decode roofline: bound by memory" in out
    assert read("moe_tokens_per_held_expert.longcat", ctx) \
        == pytest.approx(1.0)
    assert read("moe_held_experts_hit_share.longcat", ctx) \
        == pytest.approx(62.5)
    assert read("moe_zero_expert_share.longcat", ctx) \
        == pytest.approx(100 / 3.0)
    # the parent's program has no such counter, a --trace 0 run no trace
    bare = {"trace": _trace(events), "peaks": peaks,
            "compiles_in_window": {"generation_decode_steps_total": 2.0}}
    for metric in ("moe_expert_roofline.longcat",
                   "mla_paged_decode_roofline.longcat",
                   "moe_tokens_per_held_expert.longcat",
                   "moe_held_experts_hit_share.longcat",
                   "moe_zero_expert_share.longcat"):
        assert read(metric, bare) is None
    other = {"trace": _trace([["%fusion.99 = bf16[64,6144] fusion(%y)", 0,
                               1000]]),
             "peaks": peaks, "compiles_in_window": counters}
    for metric in ("moe_expert_share.longcat", "mla_prefill_share.longcat",
                   "moe_expert_roofline.longcat",
                   "mla_paged_decode_roofline.longcat"):
        assert read(metric, other) is None
        assert read(metric, {"trace": None, "peaks": peaks,
                             "compiles_in_window": counters}) is None


def test_the_cell_and_its_metrics_are_declared():
    """One configuration, one cell on one chip, every new per-layer
    metric with its ``workloads`` list, the cell-agnostic serving
    metrics reported, and not the roofline that reads nothing."""
    from benchmark.spec import Spec

    spec = Spec(ROOT)
    cell = spec.cell("longcat-serve-agent64")
    assert cell["chips"] == 1 and cell["config"] == "longcat-flash-ep32"
    assert len(cell["why"]) <= 200 and len(spec.cells) >= 6
    names = {m["name"] for m in spec.cell_metrics(cell["name"], "per_layer")}
    new = {m["name"] for m in spec.doc["per_layer"]
           if m["name"].endswith(".longcat")}
    assert len(new) == 7 and new <= names
    assert all(m["workloads"] == [cell["name"]]
               for m in spec.doc["per_layer"] if m["name"] in new)
    assert {"decode_step_ms", "kv_occupancy_peak", "peak_hbm_gb.serve",
            "device_idle_share.serve", "paged_decode_attn_share.serve",
            "stream_tokens_per_wakeup", "compiles_in_window"} <= names
    assert "mla_decode_roofline.serve" not in names
    ends = {m["name"] for m in spec.cell_metrics(cell["name"], "end_to_end")}
    assert {"serve_tokens_per_s", "setup_s"} <= ends
    for name in new:
        spec.reader(spec.metric_file(name)["reader"])
    traffic = spec.traffic(cell["traffic"])
    assert traffic["kind"] == "serve-closed" and traffic["clients"] == 64
    assert traffic["decode_buckets"] == [64]
    limits = spec.limits(cell["name"])
    assert set(limits) == {"served_token_logit_gap", "served_logit_abs_err",
                           "set_from"}


# ----------------------------------------------------------------------
# the new family rehearsed through the benchmark's own command, at the
# tiny size on the CPU (benchmark/tests has no tiny twin for it:
# PERF.md section 7)


@pytest.fixture(scope="module")
def tiny_benchmark(tmp_path_factory):
    """The real BENCHMARK.json cut to the new cell, its configuration
    the tiny one above (the real reference beside it), its traffic a
    few short requests."""
    from benchmark.spec import Spec

    root = tmp_path_factory.mktemp("tiny_benchmark")
    for sub in ("configs", "traffic", "limits"):
        os.makedirs(os.path.join(str(root), sub))
    tiny = held_config(first=0, count=8)     # a share: 8 of 16 held
    with open(os.path.join(str(root), "configs", "tiny-longcat.json"),
              "w") as f:
        json.dump(tiny, f)
    shutil.copy(REFERENCE, os.path.join(str(root), "configs",
                                        "tiny-longcat.reference.py"))
    shutil.copy(os.path.join(ROOT, "benchmark", "tests", "tiny",
                             "peaks.json"), str(root))
    with open(os.path.join(ROOT, "benchmark", "traffic",
                           "serve-agent-closed64-8k.json")) as f:
        traffic = json.load(f)
    traffic.update(
        clients=4, requests=8, stagger_s=0.01, max_total_tokens=64,
        prompt_tokens=dict(traffic["prompt_tokens"], median=12, min=4,
                           max=30),
        new_tokens=dict(traffic["new_tokens"], median=8, min=4, max=16),
        prefill_buckets=[16, 32], decode_buckets=[4], traced_seconds=0.3,
        checked_requests=3, request_timeout_s=60)
    with open(os.path.join(str(root), "traffic", "serve-tiny-8k.json"),
              "w") as f:
        json.dump(traffic, f)
    with open(os.path.join(str(root), "limits", "tiny-longcat-serve.json"),
              "w") as f:
        json.dump({"served_token_logit_gap": 1e-3,
                   "served_logit_abs_err": 1e-3}, f)
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        doc = json.load(f)
    doc["paths"], doc["run_seconds"] = ["."], 1
    doc["configs"] = [dict(c, name="tiny-longcat", source="test only",
                           file="configs/tiny-longcat.json")
                      for c in doc["configs"]
                      if c["name"] == "longcat-flash-ep32"]
    doc["workloads"] = [dict(w, name="tiny-longcat-serve",
                             config="tiny-longcat", traffic="serve-tiny-8k")
                        for w in doc["workloads"]
                        if w["name"] == "longcat-serve-agent64"]
    for m in doc["end_to_end"] + doc["per_layer"]:
        if "workloads" in m:
            m["workloads"] = ["tiny-longcat-serve"] \
                if "longcat-serve-agent64" in m["workloads"] else []
    return Spec(str(root), doc=doc)


@pytest.mark.parametrize("trace", [0, 1])
def test_new_cell_rehearsed_on_the_cpu(tiny_benchmark, trace, capsys):
    from benchmark import run

    for again in (True, False):
        result = run.run_cell(tiny_benchmark, "tiny-longcat-serve",
                              3200000019 + trace, 0.8, trace,
                              require_chip=False)
        out = capsys.readouterr().out
        # the driver books a request sent as its window closes as failed
        # (503, "draining": PERF.md section 7, seen on a loaded CPU):
        # that run says nothing of the program, so it is made once more
        if not (again and result["failed"] and "draining" in out):
            break
    assert result["correct"] is True, out
    assert result["failed"] == 0 and result["attempted"] > 0, out
    assert "served_logit_abs_err" in out and " ok" in out
    metrics = result["metrics"]
    if trace:
        assert metrics["compiles_in_window"]["value"] == 0
        assert metrics["staged_gb_per_step"]["value"] == 0
        assert 0 < metrics["moe_tokens_per_held_expert.longcat"]["value"] \
            < 16
        assert 0 < metrics["moe_held_experts_hit_share.longcat"]["value"] \
            <= 100
        # every tiny expert is held: the grouped form runs whole
        assert metrics["moe_grouped_extra_runs_per_layer"]["value"] == 0
        # 8 of the tiny router's 24 outputs are identity experts
        assert 10 < metrics["moe_zero_expert_share.longcat"]["value"] < 60
        assert metrics["kv_occupancy_peak"]["value"] > 0
        assert 4 < metrics["decode_context_tokens_mean"]["value"] < 64
        assert 0 <= metrics["decode_ahead_share"]["value"] < 100
        assert 0 <= metrics["decode_ahead_dropped_share"]["value"] < 100
        # no device trace on a CPU: nothing read, nothing raised
        for name in ("moe_expert_share.longcat", "mla_prefill_share.longcat",
                     "moe_expert_roofline.longcat",
                     "mla_paged_decode_roofline.longcat",
                     "device_idle_share.serve"):
            assert name not in metrics
    else:
        assert metrics["serve_tokens_per_s"]["value"] > 0
        assert metrics["setup_s"]["value"] > 0
    json.dumps(result)


def test_reference_one_precision_down_is_not_the_reference(family,
                                                           reference):
    """The control of the cell's limits: the reference with every
    operand rounded to float8 moves the logits by far more than bfloat16
    does.  By the median logit: at this size a rounding that flips a
    near-tie at the edge of a token's chosen experts moves single logits
    by a whole expert's contribution in either mode (the hazard the
    cell's limits file measures at the real size)."""
    cfg = family.program_config(TINY)
    params = sm.init_params(cfg, 4, jnp.bfloat16, SCALE, BIAS_SCALE)
    toks = _tokens(16, seed=4)
    exact = _reference_logits(reference, TINY, params, toks)
    err = {mode: float(np.median(np.abs(_reference_logits(
        reference, TINY, params, toks, mode) - exact)))
        for mode in ("bfloat16", "float8")}
    assert err["float8"] > 3 * err["bfloat16"] > 0, err
    with pytest.raises(ValueError, match="unknown mode"):
        reference.logits(TINY, params, toks[None], "float16")


def test_serve_tool_loads_the_family_by_configuration(tmp_path):
    """``tools/serve.py --lm name=config.json``: the configuration file
    names its family, the family's module builds the backend."""
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "serve_tool", os.path.join(ROOT, "tools", "serve.py"))
    tool = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(tool)
    path = tmp_path / "tiny.json"
    path.write_text(json.dumps(TINY))
    backend = tool.lm_backend("tiny_sc_tool", "%s:7" % path)
    assert isinstance(backend, serving.LMBackend)
    assert backend.cache.row.kind == "latent"
    assert backend.cache.num_layers == 4
    assert backend.cfg["held"] == (0, 16) and backend.cfg["seq_len"] == 64
    logits, k, v, _ = backend.prefill(np.zeros(8, np.int32), 3)
    assert logits.shape == (50,) and v is None and k.shape[0] == 4
