"""The latent-attention, sparse-expert decoder (``models/latent_moe.py``)
and what it forced: the latent paged cache, the absorbed decode, the
dropless group-limited expert layer that is told which experts it
holds, and ``LMBackend`` handed a model's definition.

Everything is held against the benchmark's plain reference
(``benchmark/configs/dots-vlm1-ep16.reference.py``, which imports
nothing of the program) at a tiny size with the published *structure*:
one dense and two expert layers, 16 experts in 4 groups of which 2 are
kept, 4 a token, 2 heads, a rope slice of 8, both ranks below the hidden
size.  float32 on the CPU, so the two sides differ by the order of
float32 additions only.
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
from jax.sharding import Mesh, PartitionSpec as P

from mxnet_tpu import observability as obs
from mxnet_tpu import serving
from mxnet_tpu.models import latent_moe as lm
from mxnet_tpu.models import transformer as tfm
from mxnet_tpu.ops.kv_cache import CacheRow, PagedKVCache
from mxnet_tpu.parallel import moe

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
YARN = {"beta_fast": 32, "beta_slow": 1, "factor": 40, "mscale": 1,
        "mscale_all_dim": 1, "original_max_position_embeddings": 4096,
        "type": "yarn"}
# the benchmark's configuration file at the tiny size: the published
# keys, the experts held (all 16 here), the deployment
TINY = {
    "family": "latent_moe", "hidden_size": 32, "intermediate_size": 64,
    "moe_intermediate_size": 16, "num_attention_heads": 2,
    "q_lora_rank": 12, "kv_lora_rank": 16, "qk_nope_head_dim": 8,
    "qk_rope_head_dim": 8, "v_head_dim": 8, "vocab_size": 50,
    "num_hidden_layers": 3, "first_k_dense_replace": 1,
    "n_routed_experts": 16, "n_shared_experts": 1,
    "num_experts_per_tok": 4, "n_group": 4, "topk_group": 2,
    "routed_scaling_factor": 2.5, "norm_topk_prob": True,
    "scoring_func": "sigmoid", "topk_method": "noaux_tc",
    "rms_norm_eps": 1e-6, "rope_theta": 10000, "rope_scaling": YARN,
    "n_positions": 64,
    "deployment": {"experts": {"published": 16, "held": 16, "first": 0},
                   "serve": {"dtype": "float32", "block_size": 4,
                             "num_blocks": 256}}}
# 0.3-wide weights and a 0.2-wide selection bias: large enough that the
# experts, the rotary slice and the bias all move the logits
SCALE, BIAS_SCALE = 0.3, 0.2


def held_config(first=0, count=16):
    cfg = copy.deepcopy(TINY)
    cfg["n_routed_experts"] = count
    cfg["deployment"]["experts"].update(held=count, first=first)
    return cfg


def program_config(cfg):
    share = cfg["deployment"]["experts"]
    return lm.lm_config(dict(cfg, n_routed_experts=share["published"]),
                        seq_len=cfg["n_positions"],
                        held=(share["first"], share["held"]))


@pytest.fixture(scope="module")
def reference():
    from benchmark.spec import load_module

    return load_module(os.path.join(
        ROOT, "benchmark", "configs", "dots-vlm1-ep16.reference.py"),
        "reference_dots")


@pytest.fixture(scope="module")
def model():
    cfg = program_config(TINY)
    return cfg, lm.init_params(cfg, 0, jnp.float32, SCALE, BIAS_SCALE)


def _tokens(n, seed=0):
    return np.random.RandomState(seed).randint(
        0, TINY["vocab_size"], n).astype(np.int32)


# ----------------------------------------------------------------------
# (a) prefill, then decode through the latent paged cache, against the
# reference's full forward


@pytest.mark.parametrize("every_row", [None, False, True],
                         ids=["by_rule", "grouped", "every_row"])
def test_prefill_then_decode_through_the_latent_cache_is_the_reference(
        model, reference, monkeypatch, every_row):
    """Logits of a 5-token prefill and of 15 decode steps through
    ``LMBackend`` and its latent pool against the reference's one
    forward over all 20 tokens.  Tolerance 1e-4 on logits of size ~1:
    both sides are float32 on the CPU and differ in the order of their
    additions (the absorbed form sums over the latent rank first, blocks
    of heads and rows there, one product here) and in ``rsqrt`` against
    ``1 / sqrt``; a dropped rotary turn, a wrong softmax scale or one
    expert missed moves them by 1e-2 or more.  By the rule the 16-row
    prefill computes every held expert over every row and the one-row
    decode step the grouped products; then each form alone."""
    cfg, params = model
    if every_row is not None:
        monkeypatch.setattr(moe, "few_rows_hit_most",
                            lambda *sizes: every_row)
    be = serving.LMBackend(params, definition=lm.lm_definition(
        cfg, jnp.float32), block_size=4, num_blocks=32, model="tiny_a")
    assert be.cache.v_pages is None
    assert be.cache.k_pages.shape == (3, 32, 4, 128)     # 24 -> one tile
    toks = _tokens(20)
    want = np.asarray(reference.logits(TINY, params, toks[None]))[0]
    assert np.abs(want).max() > 0.5
    padded = np.zeros(16, np.int32)
    padded[:5] = toks[:5]
    be.cache.allocate("s", 20)
    logits, k, v, _ = be.prefill(padded, 5)
    assert v is None
    be.cache.write_prefill("s", k, v, 5)
    np.testing.assert_allclose(logits, want[4], atol=1e-4, rtol=0)
    for t in range(5, 20):
        table = be.cache.block_table("s", be.max_blocks_per_seq)[None]
        logits, k, v, _ = be.decode([toks[t]], [t], table, [t + 1])
        np.testing.assert_allclose(logits[0], want[t], atol=1e-4, rtol=0)


def test_full_forward_is_the_reference_on_a_share(reference):
    """The same with a share of the experts held (ids 4-7 of 16): the
    reference leaves out what the absent twelve would add, as the
    program does."""
    share = held_config(first=4, count=4)
    cfg = program_config(share)
    params = lm.init_params(cfg, 1, jnp.float32, SCALE, BIAS_SCALE)
    toks = _tokens(12, seed=1)
    got = np.asarray(jax.jit(lambda p, t: lm.full_logits(p, t, cfg))(
        params, toks[None]))
    want = np.asarray(reference.logits(share, params, toks[None]))
    np.testing.assert_allclose(got, want, atol=1e-4, rtol=0)


# ----------------------------------------------------------------------
# (b) the absorbed decode is the expanded prefill


def test_absorbed_decode_equals_expanded_prefill(model):
    """One layer's attention at position 9: the absorbed form over the
    paged rows of positions 0-8 against the last row of the expanded
    form over all ten.  1e-5: the same float32 products summed in
    another order."""
    cfg, params = model
    x = jax.random.normal(jax.random.PRNGKey(3), (10, cfg["hidden_size"]))
    positions = jnp.arange(10, dtype=jnp.int32)
    expanded, rows = lm._attention_prefill(params, "l1_", x, positions, cfg)
    pages = jnp.zeros((8, 4, rows.shape[-1])).at[
        jnp.array([5, 5, 5, 5, 2, 2, 2, 2, 7]),
        jnp.array([0, 1, 2, 3, 0, 1, 2, 3, 0])].set(rows[:9])
    absorbed, row = lm._attention_decode(
        params, "l1_", x[9:], positions[9:], pages,
        jnp.array([[5, 2, 7, 0]], jnp.int32), jnp.array([10], jnp.int32),
        cfg)
    np.testing.assert_allclose(row[0], rows[9], atol=1e-6)
    np.testing.assert_allclose(absorbed[0], expanded[9], atol=1e-5, rtol=0)


# ----------------------------------------------------------------------
# (c) the router


def _route(logits, bias):
    return moe.route_group_limited(
        jnp.asarray(logits), jnp.asarray(bias), top_k=4, n_group=4,
        topk_group=2, scale=2.5)


def test_router_is_the_reference_exactly(reference):
    """Chosen sets and gates over 64 tokens: the same experts, the same
    float32 gates to the bit (both sides compute ``sigmoid``, a sum of
    four and one product)."""
    rng = np.random.RandomState(5)
    logits = rng.randn(64, 16).astype(np.float32)
    bias = (0.3 * rng.randn(16)).astype(np.float32)
    chosen, gates = _route(logits, bias)
    ref_chosen, ref_gates = reference.route(TINY, jnp.asarray(logits),
                                            jnp.asarray(bias))
    np.testing.assert_array_equal(np.asarray(chosen), np.asarray(ref_chosen))
    np.testing.assert_array_equal(np.asarray(gates), np.asarray(ref_gates))
    np.testing.assert_allclose(np.asarray(gates).sum(-1), 2.5, rtol=1e-6)
    # group-limited: the four chosen lie in two groups of four
    assert all(len({e // 4 for e in row}) <= 2 for row in np.asarray(chosen))


def test_selection_bias_changes_the_choice_and_not_the_gate(reference):
    """Expert 5 scores below expert 4 and is chosen in its place once
    its bias lifts it; the gates are the scores without the bias."""
    logits = np.full((1, 16), -4.0, np.float32)
    logits[0, [0, 1, 2, 4]] = [2.0, 1.5, 1.0, 0.5]
    logits[0, 5] = 0.4
    chosen, gates = _route(logits, np.zeros(16, np.float32))
    assert sorted(np.asarray(chosen)[0]) == [0, 1, 2, 4]
    bias = np.zeros(16, np.float32)
    bias[5] = 0.2
    chosen_b, gates_b = _route(logits, bias)
    assert sorted(np.asarray(chosen_b)[0]) == [0, 1, 2, 5]
    s = 1 / (1 + np.exp(-logits[0].astype(np.float64)))
    want = 2.5 * s[[0, 1, 2, 5]] / s[[0, 1, 2, 5]].sum()
    order = np.argsort(np.asarray(chosen_b)[0])
    np.testing.assert_allclose(np.asarray(gates_b)[0][order], want,
                               rtol=1e-6)
    ref_chosen, ref_gates = reference.route(TINY, jnp.asarray(logits),
                                            jnp.asarray(bias))
    np.testing.assert_array_equal(np.asarray(chosen_b),
                                  np.asarray(ref_chosen))
    np.testing.assert_array_equal(np.asarray(gates_b), np.asarray(ref_gates))


# ----------------------------------------------------------------------
# (d) the shares add up, (e) no token is dropped


def _layer_weights(params, prefix="l1_"):
    return {k[len(prefix):]: v for k, v in params.items()
            if k.startswith(prefix)}


def _reference_layer(reference, cfg_file, w, h):
    return np.asarray(reference._expert_layer(
        cfg_file, w, h, reference._Math("float32")))


def _share_part(w, h, chosen, gates, first, count, every_row=False):
    """One share's routed part: its own slice of the experts."""
    part, counts = moe.dropless_experts(
        h, chosen, gates, w["experts_gate_weight"][first:first + count],
        w["experts_up_weight"][first:first + count],
        w["experts_down_weight"][first:first + count], (first, count),
        every_row=every_row)
    return np.asarray(part), np.asarray(counts)


# the expert layer's two forms: grouped products over the sorted pairs
# (a prefill), every held expert over every row (a decode step)
FORMS = pytest.mark.parametrize("every_row", [False, True],
                                ids=["grouped", "every_row"])


@FORMS
@pytest.mark.parametrize("shares", [1, 2, 4, 8, 16])
def test_the_shares_add_up_to_the_uncut_layer(model, reference, shares,
                                              every_row):
    """Every split of the 16 experts into equal shares: the shares'
    routed parts, plus the shared expert counted once, are the uncut
    reference's layer; the shares' local pairs are all the pairs."""
    cfg, params = model
    w = _layer_weights(params)
    h = jax.random.normal(jax.random.PRNGKey(7), (24, cfg["hidden_size"]))
    logits = jnp.einsum("nc,ec->ne", h, w["router_weight"])
    chosen, gates = _route(logits, w["router_bias"])
    count = 16 // shares
    parts = [_share_part(w, h, chosen, gates, i * count, count, every_row)
             for i in range(shares)]
    shared = np.asarray(moe.swiglu(h, w["shared_gate_weight"],
                                   w["shared_up_weight"],
                                   w["shared_down_weight"]))
    total = sum(p for p, _ in parts) + shared
    want = _reference_layer(reference, TINY, w, h)
    np.testing.assert_allclose(total, want, atol=2e-5, rtol=0)
    assert sum(c[1] for _, c in parts) == 24 * 4        # every pair, once
    assert all(c[0] == 24 * 4 for _, c in parts)
    # and a share alone is the reference told of the same share
    cut = held_config(first=count, count=count) if shares > 1 else TINY
    first = cut["deployment"]["experts"]["first"]
    w_cut = dict(w, **{k: w[k][first:first + count] for k in (
        "experts_gate_weight", "experts_up_weight", "experts_down_weight")})
    np.testing.assert_allclose(
        parts[min(1, shares - 1)][0] + shared,
        _reference_layer(reference, cut, w_cut, h), atol=2e-5, rtol=0)


@FORMS
@pytest.mark.parametrize("tokens", [1, 24, 200])
def test_no_token_is_dropped_at_any_skew(model, reference, tokens,
                                         every_row):
    """All tokens to one expert (and their other three choices to three
    more): every pair is computed, at any number of tokens."""
    cfg, params = model
    w = _layer_weights(params)
    h = jax.random.normal(jax.random.PRNGKey(8), (tokens, cfg["hidden_size"]))
    chosen = jnp.tile(jnp.array([[3, 0, 1, 2]], jnp.int32), (tokens, 1))
    gates = jnp.tile(jnp.array([[1.0, 0.5, 0.25, 0.75]]), (tokens, 1))
    part, counts = _share_part(w, h, chosen, gates, 0, 16, every_row)
    assert list(counts) == [4 * tokens, 4 * tokens, 4, 1, 0]
    want = sum(
        g * np.asarray(reference._swiglu(
            reference._Math("float32"), h, w["experts_gate_weight"][e],
            w["experts_up_weight"][e], w["experts_down_weight"][e],
            "tc,cf->tf", "tf,fc->tc"))
        for e, g in zip([3, 0, 1, 2], [1.0, 0.5, 0.25, 0.75]))
    np.testing.assert_allclose(part, want, atol=2e-5, rtol=0)
    # pad rows of a bucket are routed nowhere
    valid = jnp.arange(tokens) < max(1, tokens // 2)
    part_v, counts_v = moe.dropless_experts(
        h, chosen, gates, w["experts_gate_weight"], w["experts_up_weight"],
        w["experts_down_weight"], (0, 16), valid=valid,
        every_row=every_row)
    assert int(counts_v[1]) == 4 * max(1, tokens // 2)
    assert not np.asarray(part_v)[max(1, tokens // 2):].any()


def test_which_calls_compute_every_row():
    """The cell's decode step (64 rows, 8 of 256) computes every held
    expert over every row; a step of few rows reaches few experts and a
    prefill is bound by its operations: both keep the grouped form."""
    assert moe.few_rows_hit_most(64, 8, 256)
    assert moe.few_rows_hit_most(22, 8, 256)
    assert not moe.few_rows_hit_most(16, 8, 256)
    assert not moe.few_rows_hit_most(1, 8, 256)
    assert not moe.few_rows_hit_most(256, 8, 256)
    assert not moe.few_rows_hit_most(2048, 8, 256)


@FORMS
def test_sharded_expert_layer_over_an_expert_axis(model, reference,
                                                  every_row):
    """Over an ``expert`` mesh axis of four the same function is the
    sharded layer: each member holds four experts and the parts are
    summed on the axis."""
    from jax import shard_map

    cfg, params = model
    w = _layer_weights(params)
    h = jax.random.normal(jax.random.PRNGKey(9), (24, cfg["hidden_size"]))
    chosen, gates = _route(jnp.einsum("nc,ec->ne", h, w["router_weight"]),
                           w["router_bias"])
    mesh = Mesh(np.array(jax.devices()[:4]), ("expert",))

    def member(gate_w, up_w, down_w):
        return moe.dropless_experts(h, chosen, gates, gate_w, up_w, down_w,
                                    (None, 4), expert_axis="expert",
                                    every_row=every_row)[0]

    routed = shard_map(member, mesh=mesh, in_specs=(P("expert"),) * 3,
                       out_specs=P(), check_vma=False)(
        w["experts_gate_weight"], w["experts_up_weight"],
        w["experts_down_weight"])
    whole, _ = _share_part(w, h, chosen, gates, 0, 16)
    np.testing.assert_allclose(np.asarray(routed), whole, atol=2e-5, rtol=0)


# ----------------------------------------------------------------------
# (f) YaRN, by hand for the published rope_scaling


def test_yarn_frequencies_and_scale_by_hand():
    """rope 64, theta 10000, factor 40, original 4096, beta 32 / 1: the
    correction dimensions are floor(64 ln(4096 / (32 * 2 pi)) / (2 ln
    10000)) = 10 and ceil(64 ln(4096 / (2 pi)) / (2 ln 10000)) = 23, so
    pairs 0-10 keep 1 / theta^(2i/64), pairs 23-31 are divided by 40,
    and pair 16 sits 6/13 of the way.  m(1) = 0.1 ln 40 + 1 = 1.368888;
    cos and sin are scaled by m(1) / m(1) = 1 and the softmax by
    192^-0.5 * 1.368888^2 = 0.1352337."""
    cfg = {"qk_rope_head_dim": 64, "qk_nope_head_dim": 128,
           "rope_theta": 10000, "rope_scaling": YARN}
    inv, scale = lm.yarn_inv_freq(cfg)
    assert scale == 1.0 and inv.shape == (32,)
    plain = 10000.0 ** (-np.arange(32) / 32.0)
    np.testing.assert_allclose(inv[:11], plain[:11], rtol=1e-6)
    np.testing.assert_allclose(inv[23:], plain[23:] / 40, rtol=1e-6)
    np.testing.assert_allclose(inv[0], 1.0)
    np.testing.assert_allclose(inv[31], 1.3335214e-4 / 40, rtol=1e-5)
    ramp = 6.0 / 13.0
    np.testing.assert_allclose(
        inv[16], 0.01 * (1 - ramp) + 0.01 / 40 * ramp, rtol=1e-5)
    assert math.isclose(lm.softmax_scale(cfg), 0.1352337, rel_tol=1e-6)


def test_rotary_turns_pairs_and_keeps_products(reference):
    """Pairs (2i, 2i+1) turn by position x frequency; a query at
    position 7 times a key at position 3 depends on 7 - 3 alone; the
    reference's turn gives the same products."""
    cfg = program_config(TINY)
    x = jax.random.normal(jax.random.PRNGKey(2), (2, 8))
    inv, _ = lm.yarn_inv_freq(cfg)
    at = jnp.array([7, 3], jnp.int32)
    turned = np.asarray(lm._rotate(x, at, cfg))
    angle = 7 * inv[1]
    np.testing.assert_allclose(
        turned[0, 1], x[0, 2] * np.cos(angle) - x[0, 3] * np.sin(angle),
        rtol=1e-5)
    np.testing.assert_allclose(
        turned[0, 5], x[0, 3] * np.cos(angle) + x[0, 2] * np.sin(angle),
        rtol=1e-5)
    moved = np.asarray(lm._rotate(x, at + 11, cfg))
    np.testing.assert_allclose(turned[0] @ turned[1], moved[0] @ moved[1],
                               rtol=1e-4)
    cos, sin, _ = reference.yarn(TINY, 19)
    ref = np.asarray(reference._apply_rotary(x, cos[np.asarray(at)],
                                             sin[np.asarray(at)]))
    np.testing.assert_allclose(turned[0] @ turned[1], ref[0] @ ref[1],
                               rtol=1e-4)


# ----------------------------------------------------------------------
# (g) GPT-2 through the re-parameterised LMBackend is what it was


def test_gpt2_through_a_definition_is_bitwise_what_it_was():
    """``LMBackend`` no longer imports a model.  The programs it builds
    from the transformer's definition give, bit for bit, the logits and
    cache rows of the programs it used to build itself."""
    cfg = tfm.lm_config(num_classes=64, seq_len=48, num_embed=16,
                        num_heads=2, num_layers=2)
    params = tfm.init_lm_params(cfg, seed=0)
    be = serving.LMBackend(params, cfg, block_size=4, num_blocks=16)
    assert be.cache.row == CacheRow("kv", 16, np.float32, 2)

    def rows(kv):
        return kv.reshape(kv.shape[0], -1, kv.shape[-2] * kv.shape[-1])

    @jax.jit
    def old_prefill(params, tokens, length):
        logits, k, v = tfm.lm_prefill(params, tokens[None], cfg)
        return logits[0, length - 1], rows(k), rows(v)

    @jax.jit
    def old_decode(params, tokens, positions, k_pages, v_pages, tables,
                   lens):
        logits, k, v = tfm.lm_decode_step(params, tokens, positions,
                                          k_pages, v_pages, tables, lens,
                                          cfg)
        return logits, rows(k), rows(v)

    toks = np.arange(3, 11, dtype=np.int32)
    logits, k, v, _ = be.prefill(toks, 6)
    for got, want in zip((logits, k, v), old_prefill(
            be.params, toks, np.int32(6))):
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    be.cache.allocate("s", 12)
    be.cache.write_prefill("s", k, v, 6)
    table = be.cache.block_table("s", be.max_blocks_per_seq)[None]
    want = old_decode(be.params, np.array([9], np.int32),
                      np.array([6], np.int32), be.cache.k_pages,
                      be.cache.v_pages, table, np.array([7], np.int32))
    got = be.decode([9], [6], table, [7])[:3]
    for a, b in zip(got, want):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert be.infer({"data": toks[None]})[0][0].shape == (1, 8, 64)
    with pytest.raises(Exception, match="int8_head"):
        serving.LMBackend(params, definition=be.definition, int8_head=True)


# ----------------------------------------------------------------------
# the latent pool, the counters, the served path


def test_latent_pool_is_one_pool_of_rows():
    row = CacheRow("latent", 640, jnp.bfloat16, 1)
    cache = PagedKVCache(num_layers=2, row=row, block_size=4, num_blocks=8,
                         model="tiny_pool")
    assert cache.v_pages is None and cache.k_pages.dtype == jnp.bfloat16
    assert cache.pool_bytes == 2 * 8 * 4 * 640 * 2 and row.bytes == 1280
    cache.allocate("s", 6)
    rows = jnp.arange(2 * 8 * 640, dtype=jnp.float32).reshape(2, 8, 640) / 64
    cache.write_prefill("s", rows, None, 6)
    table = cache.block_table("s", 2)
    got = np.asarray(cache.k_pages[:, table].reshape(2, 8, 640), np.float32)
    np.testing.assert_array_equal(got[:, :6], np.asarray(
        rows.astype(jnp.bfloat16), np.float32)[:, :6])
    assert not got[:, 6:].any()
    text = obs.REGISTRY.render()
    assert 'kv_cache_row_bytes{model="tiny_pool"} 1280' in text


def _counter(name, model):
    for line in obs.REGISTRY.render().splitlines():
        if line.startswith('%s{model="%s"}' % (name, model)):
            return float(line.rsplit(" ", 1)[1])
    return 0.0


def test_served_through_the_scheduler_and_counted(model):
    """The normal path: ``GenerationScheduler`` -> ``LMBackend`` -> the
    latent pool, greedy tokens equal to a full forward's, nothing
    compiled after warm-up, and the expert counters booked from the
    counts that rode back with the logits."""
    cfg, params = model
    be = serving.LMBackend(params, definition=lm.lm_definition(
        cfg, jnp.float32), block_size=4, num_blocks=64, model="tiny_served")
    sched = serving.GenerationScheduler(name="latent")
    try:
        sched.register("tiny_served", be, decode_buckets=[2, 4],
                       prefill_buckets=[8, 16])
        sched.warmup("tiny_served")
        cold = _counter("generation_compiles_total", "tiny_served")
        before = {n: _counter(n, "tiny_served") for n in moe.EXPERT_COUNTS}
        prompts = [_tokens(6, 11), _tokens(11, 12), _tokens(3, 13)]
        reqs = [sched.submit("tiny_served", p, max_new_tokens=6)
                for p in prompts]
        outs = [r.result(timeout=120) for r in reqs]
        forward = jax.jit(lambda t: lm.full_logits(params, t, cfg)[0])
        for prompt, out in zip(prompts, outs):
            seq = np.zeros(24, np.int32)     # causal: the pad is unseen
            seq[:len(prompt)], n = prompt, len(prompt)
            for tok in out:
                want = np.asarray(forward(seq[None]))[n - 1]
                assert want[tok] >= want.max() - 1e-4
                seq[n], n = tok, n + 1
        assert _counter("generation_compiles_total", "tiny_served") == cold
        after = {n: _counter(n, "tiny_served") - before[n]
                 for n in moe.EXPERT_COUNTS}
        layer_calls = after["moe_layer_steps_total"]
        assert layer_calls >= 2 * (3 + 5)        # 3 prefills, >= 5 steps
        # all 16 experts are held: every pair is local
        assert after["moe_assignments_total"] \
            == after["moe_local_assignments_total"] >= 4 * 2 * (20 + 15)
        assert 0 < after["moe_local_experts_hit_total"] <= 16 * layer_calls
        assert _counter("generation_decode_context_tokens_total",
                        "tiny_served") > 0
    finally:
        sched.close()


# ----------------------------------------------------------------------
# the benchmark's arithmetic and readers for what this model adds


def _published():
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "dots-vlm1-ep16.json")) as f:
        return json.load(f)


def test_configuration_keeps_the_published_widths():
    """Every number of the catalog's row is in the file under its key,
    but for the keys ``reduced`` names; no width is among them."""
    cfg = _published()
    path = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(path):
        pytest.skip("no catalog here")
    with open(path) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "dots.vlm1.inst")
    assert cfg["source"] == row["source_url"]
    for key, value in row["config"].items():
        if key in cfg["reduced"]:
            assert cfg[key] != value and cfg["published"][key] == value
        else:
            assert cfg[key] == value, key
    assert not [k for k in cfg["reduced"]
                if k.endswith(("_dim", "_rank", "_size"))
                and k != "vocab_size"]
    share = cfg["deployment"]["experts"]
    assert share == {"published": 256, "held": cfg["n_routed_experts"],
                     "first": 0}
    assert cfg["vocab_size"] * cfg["deployment"]["vocab_shards"] == 129280


def test_parameter_count_of_the_cut():
    """ISSUE 26's arithmetic: 187.1M of attention a layer, 583.5M the
    dense layer, 937.6M an expert layer with 16 held, 231.7M of
    embedding and head: 5,503M parameters, 11.0 GB in bfloat16."""
    from benchmark.spec import load_module

    family = load_module(os.path.join(ROOT, "benchmark", "models",
                                      "latent_moe.py"), "family")
    shapes = family.weight_shapes(_published())
    count = {k: int(np.prod(s)) for k, s in shapes.items()}
    layer = lambda i: sum(v for k, v in count.items()        # noqa: E731
                          if k.startswith("l%d_" % i))
    attention = sum(count["l0_" + k] for k in (
        "q_a_weight", "q_b_weight", "kv_a_weight", "kv_b_weight",
        "o_weight"))
    assert attention == 187105280
    assert abs(layer(0) - 583.5e6) < 0.1e6
    assert abs(layer(1) - 937.6e6) < 0.1e6 and layer(1) == layer(5)
    assert count["embed_weight"] + count["pred_weight"] == 231669760
    assert abs(sum(count.values()) - 5503e6) < 1e6


def test_cost_arithmetic():
    from benchmark import latent_moe_costs as costs

    cfg = _published()
    assert costs.expert_weight_bytes(cfg) == 3 * 7168 * 2048 * 2 == 88080384
    assert costs.expert_flops_per_assignment(cfg) == 88080384
    ops, moved = costs.routed_experts_cost(cfg, experts_hit=14,
                                           local_assignments=32)
    assert ops == 32 * 88080384
    assert moved == 14 * 88080384 + 32 * (2 * 7168 + 3 * 2048) * 2
    # 64 rows at 1,500 cached tokens each, one layer
    ops, moved = costs.latent_decode_cost(cfg, context_tokens=96000, rows=64)
    assert ops == 2 * 96000 * 128 * (576 + 512)
    assert moved == (96000 * 576 + 64 * 128 * (576 + 512)) * 2
    # a decode step's held experts are bandwidth-bound far below 240
    # tokens an expert: 88 MB at 819 GB/s against 88 MFLOP a token
    from benchmark import flops

    peaks = {"flops_per_s": {"bfloat16": 197e12}, "hbm_bytes_per_s": 819e9}
    for tokens, bound in ((2, "memory"), (200, "memory"), (400, "compute")):
        o, b = costs.routed_experts_cost(cfg, 1, tokens)
        assert flops.roofline_seconds(o, b, peaks)[1] == bound


def _trace(events):
    return {"window_ns": [0, 1000000], "devices": {"0": events}, "host": []}


def test_readers_of_the_new_metrics(capsys):
    """On a made-up trace: the shares count what their patterns name,
    the rooflines come out under 100% and say which peak bounds them,
    and every reader returns nothing where there is nothing to read (a
    program without the counters, a run without a trace)."""
    from benchmark.spec import Spec

    spec = Spec(ROOT)
    peaks = spec.peaks("TPU v5 lite")

    def read(metric, ctx):
        doc = spec.metric_file(metric)
        return spec.reader(doc["reader"])(ctx, doc.get("params", {}))

    gather = "%fusion.10 = bf16[16384,16,640]{3,2,1,0} fusion(%p, %t)"
    scores = "%fusion.11 = f32[64,128,4096]{2,1,0} fusion(bf16[64,128,640] %q)"
    events = [["%ragged-dot-metadata = (s32[17]) custom-call(%x)", 0, 1000],
              ["%ragged-dot-none = f32[512,2048] custom-call(%a)", 1000, 90000],
              ["%ragged-dot-none.1 = f32[512,2048] custom-call(%a)", 91000,
               90000],
              ["%ragged-dot-none.2 = bf16[512,7168] custom-call(%a)", 181000,
               90000],
              # a decode step's three products over every held expert
              ["%convolution_bitcast_fusion.9 = f32[16,64,2048]{2,1,0} "
               "fusion(bf16[16,7168,2048] %w, bf16[64,7168] %x)", 210000,
               20000],
              ["%fusion.293 = bf16[16,64,2048]{2,1,0} fusion("
               "bf16[16,7168,2048] %w, f32[16,64,2048] %g)", 230000, 20000],
              ["%fusion.97 = bf16[64,7168]{1,0} fusion(bf16[16,64,2048] %h, "
               "bf16[16,2048,7168] %w)", 250000, 20000],
              [gather, 300000, 60000], [scores, 360000, 40000],
              ["%latent_prefill_attention.3 = bf16[128,4096,128] "
               "custom-call(%q)", 500000, 50000],
              ["%fusion.99 = bf16[64,7168] fusion(%y)", 600000, 100000]]
    counters = {"moe_layer_steps_total": 10.0,
                "moe_local_experts_hit_total": 140.0,
                "moe_local_assignments_total": 320.0,
                "moe_grouped_extra_runs_total": 1.0,
                "generation_decode_steps_total": 2.0,
                "generation_decode_context_tokens_total": 2 * 96000.0,
                "generation_tokens_total": 128.0}
    ctx = {"trace": _trace(events), "peaks": peaks,
           "compiles_in_window": counters}
    assert read("moe_expert_share.serve", ctx) == pytest.approx(33.1)
    assert read("mla_attn_share.serve", ctx) == pytest.approx(15.0)
    # two layer calls traced of ten (a prefill's three grouped kernels,
    # a decode step's three batched products): 28 experts hit, 64 pairs
    least = (28 * 88080384 + 64 * 20480 * 2) / 819e9
    assert read("moe_expert_roofline.serve", ctx) == pytest.approx(
        100 * least / 330e-6)
    # one (layer, step) traced: 96,000 cached rows
    least = (96000 * 576 + 64 * 128 * 1088) * 2 / 819e9
    assert read("mla_decode_roofline.serve", ctx) == pytest.approx(
        100 * least / 100e-6)
    out = capsys.readouterr().out
    assert "expert roofline: bound by memory" in out
    assert "latent decode roofline: bound by memory" in out
    assert read("moe_tokens_per_held_expert", ctx) == pytest.approx(2.0)
    assert read("moe_held_experts_hit_share", ctx) == pytest.approx(87.5)
    # one layer call of the ten overflowed into a second run (PR 43)
    assert read("moe_grouped_extra_runs_per_layer", ctx) == pytest.approx(0.1)
    # the parent's program has no such counter, a --trace 0 run no trace
    bare = {"trace": _trace(events), "peaks": peaks,
            "compiles_in_window": {"generation_decode_steps_total": 2.0}}
    for metric in ("moe_expert_roofline.serve", "mla_decode_roofline.serve",
                   "moe_tokens_per_held_expert",
                   "moe_held_experts_hit_share",
                   "moe_grouped_extra_runs_per_layer"):
        assert read(metric, bare) is None
    other = {"trace": _trace(events[-1:]), "peaks": peaks,
             "compiles_in_window": counters}
    for metric in ("moe_expert_share.serve", "mla_attn_share.serve",
                   "moe_expert_roofline.serve", "mla_decode_roofline.serve"):
        assert read(metric, other) is None
        assert read(metric, {"trace": None, "peaks": peaks,
                             "compiles_in_window": counters}) is None


# ----------------------------------------------------------------------
# the new family rehearsed through the benchmark's own command, at the
# tiny size on the CPU (benchmark/tests has no tiny twin for a fourth
# cell: PERF.md section 7)


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
    with open(os.path.join(str(root), "configs", "tiny-dots.json"),
              "w") as f:
        json.dump(tiny, f)
    shutil.copy(os.path.join(ROOT, "benchmark", "configs",
                             "dots-vlm1-ep16.reference.py"),
                os.path.join(str(root), "configs",
                             "tiny-dots.reference.py"))
    shutil.copy(os.path.join(ROOT, "benchmark", "tests", "tiny",
                             "peaks.json"), str(root))
    with open(os.path.join(ROOT, "benchmark", "traffic",
                           "serve-chat-closed64-4k.json")) as f:
        traffic = json.load(f)
    traffic.update(
        clients=4, requests=8, stagger_s=0.01, max_total_tokens=64,
        prompt_tokens=dict(traffic["prompt_tokens"], median=12, min=4,
                           max=30),
        new_tokens=dict(traffic["new_tokens"], median=8, min=4, max=16),
        prefill_buckets=[16, 32], decode_buckets=[4], traced_seconds=0.3,
        checked_requests=3, request_timeout_s=60)
    with open(os.path.join(str(root), "traffic", "serve-tiny-4k.json"),
              "w") as f:
        json.dump(traffic, f)
    with open(os.path.join(str(root), "limits", "tiny-dots-serve.json"),
              "w") as f:
        json.dump({"served_token_logit_gap": 1e-3,
                   "served_logit_abs_err": 1e-3}, f)
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        doc = json.load(f)
    doc["paths"], doc["run_seconds"] = ["."], 1
    doc["configs"] = [dict(c, name="tiny-dots", source="test only",
                           file="configs/tiny-dots.json")
                      for c in doc["configs"]
                      if c["name"] == "dots-vlm1-ep16"]
    doc["workloads"] = [dict(w, name="tiny-dots-serve", config="tiny-dots",
                             traffic="serve-tiny-4k")
                        for w in doc["workloads"]
                        if w["name"] == "dots-vlm1-serve-chat64"]
    for m in doc["end_to_end"] + doc["per_layer"]:
        if "workloads" in m:
            m["workloads"] = ["tiny-dots-serve"] \
                if "dots-vlm1-serve-chat64" in m["workloads"] else []
    return Spec(str(root), doc=doc)


@pytest.mark.parametrize("trace", [0, 1])
def test_new_cell_rehearsed_on_the_cpu(tiny_benchmark, trace, capsys):
    from benchmark import run

    result = run.run_cell(tiny_benchmark, "tiny-dots-serve",
                          3000000019 + trace, 0.8, trace,
                          require_chip=False)
    out = capsys.readouterr().out
    assert result["correct"] is True, out
    assert result["failed"] == 0 and result["attempted"] > 0, out
    assert "served_logit_abs_err" in out and " ok" in out
    metrics = result["metrics"]
    if trace:
        assert metrics["compiles_in_window"]["value"] == 0
        assert metrics["staged_gb_per_step"]["value"] == 0
        assert 0 < metrics["moe_tokens_per_held_expert"]["value"] < 16
        assert 0 < metrics["moe_held_experts_hit_share"]["value"] <= 100
        # every tiny expert is held: the grouped form runs whole
        assert metrics["moe_grouped_extra_runs_per_layer"]["value"] == 0
        assert metrics["kv_occupancy_peak"]["value"] > 0
        # short prompts, 4-16 new tokens: what a row attends over a step
        assert 4 < metrics["decode_context_tokens_mean"]["value"] < 64
        # the share of decode calls a queued step answered (4 callers
        # on 4 rows with 4-16 new tokens: a row ends every other step)
        assert 0 <= metrics["decode_ahead_share"]["value"] < 100
        assert metrics["decode_ahead_dropped_share"]["value"] == 0
        # no device trace on a CPU: nothing read, nothing raised
        for name in ("moe_expert_share.serve", "mla_decode_roofline.serve",
                     "device_idle_share.serve"):
            assert name not in metrics
    else:
        assert metrics["serve_tokens_per_s"]["value"] > 0
        assert metrics["ttft_p50_ms"]["value"] > 0
        assert metrics["setup_s"]["value"] > 0
    json.dumps(result)


def test_reference_one_precision_down_is_not_the_reference(reference):
    """The control of the cell's limits: the reference with every
    operand rounded to float8 moves the logits by far more than bfloat16
    does.  By the median logit: at this size a rounding that flips a
    near-tie at the edge of a token's chosen experts moves single logits
    by a whole expert's contribution in either mode (the hazard the
    cell's limits file measures at the real size)."""
    cfg = program_config(TINY)
    params = lm.init_params(cfg, 4, jnp.bfloat16, SCALE, BIAS_SCALE)
    toks = _tokens(16, seed=4)[None]
    exact = np.asarray(reference.logits(TINY, params, toks, "float32"))
    err = {mode: float(np.median(np.abs(np.asarray(
        reference.logits(TINY, params, toks, mode)) - exact)))
        for mode in ("bfloat16", "float8")}
    assert err["float8"] > 3 * err["bfloat16"] > 0, err
    with pytest.raises(ValueError, match="unknown mode"):
        reference.logits(TINY, params, toks, "float16")


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
    backend = tool.lm_backend("tiny_tool", "%s:7" % path)
    assert isinstance(backend, serving.LMBackend)
    assert backend.cache.row.kind == "latent"
    assert backend.cfg["held"] == (0, 16) and backend.cfg["seq_len"] == 64
    logits, k, v, _ = backend.prefill(np.zeros(8, np.int32), 3)
    assert logits.shape == (50,) and v is None
