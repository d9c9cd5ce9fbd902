"""The kernels, the arithmetic and the readers that the decoder of
sliding-window and global attention over ReGLU experts
(``models/window_moe.py``) brought, which need no model: the decode walk
over a ring of blocks against the XLA body and the plain sum, the flash
kernel over a band, the expert layer's activation as an argument, the
configuration with its parameter count and its bytes, and the readers of
the cell's metrics.  The model against its plain reference, the cache's
layer groups and the served path are in ``test_window_moe.py`` (one file
until PR 43; two, so that neither holds a worker of the tier-1 run for
five minutes)."""

import json
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from mxnet_tpu.models import window_moe as wm
from mxnet_tpu.ops import attention as att
from mxnet_tpu.ops import paged_attention as paged
from mxnet_tpu.parallel import moe

from test_window_moe import ROOT, _family, _published

# ----------------------------------------------------------------------
# (e) the walk over a ring against the XLA body and the plain sum


def _ring_case(ctx, window, blk, heads, groups, dim, dtype="float32",
               seed=0):
    """A pool of garbage in which every sequence's cached tokens were
    written in order through its ring (a later token over an older
    one), the tables, and the plain per-row answer."""
    rng = np.random.RandomState(seed)
    ring = window // blk + 1
    width = groups * dim
    k_pool = rng.standard_normal((len(ctx) * ring + 1, blk, width)) * 4
    v_pool = rng.standard_normal(k_pool.shape) * 4
    tables = np.zeros((len(ctx), ring), np.int32)
    q = rng.standard_normal((len(ctx), heads, dim))
    keys = [rng.standard_normal((c, groups, dim)) for c in ctx]
    values = [rng.standard_normal((c, groups, dim)) for c in ctx]
    want = np.zeros((len(ctx), heads, dim))
    nxt, per = 1, heads // groups
    for i, c in enumerate(ctx):
        r = min(ring, -(-c // blk))
        tables[i, :r] = np.arange(nxt, nxt + r)
        nxt += r
        for j in range(c - 1):
            at = tables[i, (j // blk) % ring], j % blk
            k_pool[at], v_pool[at] = keys[i][j].ravel(), values[i][j].ravel()
        lo = max(0, c - window)
        for h in range(heads):
            s = keys[i][lo:, h // per] @ q[i, h] / np.sqrt(dim)
            p = np.exp(s - s.max())
            want[i, h] = (p / p.sum()) @ values[i][lo:, h // per]
    args = [jnp.asarray(a, jnp.float32).astype(dtype) for a in (
        q, np.stack([k[-1] for k in keys]), np.stack([v[-1] for v in values]),
        k_pool, v_pool)]
    return args + [jnp.asarray(tables), jnp.asarray(ctx, jnp.int32)], want


_RAGGED = {"under": (1, 2, 16, 17, 31), "at": (32, 33, 47, 48),
           "over": (49, 50, 64, 65, 100, 200)}


@pytest.mark.parametrize("ctx", sorted(_RAGGED))
def test_the_ring_walk_is_the_xla_body_and_the_plain_sum(ctx):
    """28 query heads over 4 key-value heads of 128 (seven a head, each
    run padded to a tile), a window of 32 over blocks of 16, contexts
    under, at and over the window and past the ring's wrap: the kernel
    (under the interpreter) against the XLA body against the softmax
    written out over the keys the window lets a row see."""
    args, want = _ring_case(_RAGGED[ctx], 32, 16, 28, 4, 128)
    scale = 128 ** -0.5
    ref = paged._gqa_decode_xla(*args, scale, 32)
    got = paged._gqa_decode_pallas(*args, scale, True, window=32)
    np.testing.assert_allclose(np.asarray(ref), want, atol=2e-5, rtol=0)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), atol=2e-5,
                               rtol=0)


def test_the_ring_walk_at_the_served_window_in_bfloat16():
    """The served sizes a row: a window of 4,096 over blocks of 16, a
    ring of 257 blocks, 512-wide bfloat16 rows, contexts either side of
    the window and of the ring's first wrap."""
    args, want = _ring_case((100, 4096, 4113, 5000), 4096,
                            16, 28, 4, 128, "bfloat16", seed=1)
    scale = 128 ** -0.5
    ref = paged._gqa_decode_xla(*args, scale, 4096)
    got = paged._gqa_decode_pallas(*args, scale, True, window=4096)
    np.testing.assert_allclose(np.asarray(ref, np.float32), want, atol=3e-2,
                               rtol=0)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(ref, np.float32), atol=3e-2,
                               rtol=0)


def test_seven_queries_a_head_without_a_window_take_the_padded_runs():
    """The global layers' walk at 28 heads over 4: the runs of seven
    rows are padded to eight inside the kernel's call and the dead rows
    dropped from what it returns."""
    args, _ = _ring_case((1, 17, 40), 4096, 16, 28, 4, 128, seed=2)
    scale = 128 ** -0.5
    ref = paged._gqa_decode_xla(*args, scale)
    got = paged._gqa_decode_pallas(*args, scale, True)
    assert got.shape == (3, 28, 128)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), atol=2e-5,
                               rtol=0)


def test_where_pallas_runs_a_window_layer_takes_the_ring_walk(monkeypatch):
    """The rule: with a window the public function runs the walk over
    the ring under its own scope, and refuses heads that are not whole
    lane tiles; off the chip it runs the XLA body with the same mask."""
    from mxnet_tpu.ops import platform

    args, want = _ring_case((40, 70), 32, 16, 14, 2, 128, seed=3)
    scale = 128 ** -0.5
    off = paged.gqa_paged_decode_attention(*args, scale, window=32)
    np.testing.assert_allclose(np.asarray(off), want, atol=2e-5, rtol=0)
    monkeypatch.setattr(platform, "pallas_mode", lambda: "interpret")
    jax.clear_caches()
    try:
        text = jax.jit(lambda *a: paged.gqa_paged_decode_attention(
            *a, scale, window=32)).lower(*args).as_text(debug_info=True)
        assert "paged_decode_gqa_window" in text
        on = paged.gqa_paged_decode_attention(*args, scale, window=32)
        np.testing.assert_allclose(np.asarray(on), want, atol=2e-5, rtol=0)
        narrow, _ = _ring_case((40,), 32, 16, 4, 2, 64, seed=4)
        with pytest.raises(NotImplementedError, match="lane tiles"):
            paged.gqa_paged_decode_attention(*narrow, 0.125, window=32)
    finally:
        jax.clear_caches()
    with pytest.raises(ValueError, match="whole blocks"):
        paged.gqa_paged_decode_attention(*args, scale, window=40)


# ----------------------------------------------------------------------
# (f) the band in the flash kernel


def _brute_walk(row0, rows, chunk, n, window, kv_len=None):
    """The walk of a run from the mask itself: the chunks that hold a
    pair the band lets through, and whether they hold one it does not."""
    r = np.arange(row0, row0 + rows)[:, None]
    walk = []
    for c in range(n):
        j = np.arange(c * chunk, (c + 1) * chunk)[None]
        seen = (j <= r) & (r - j < window) if window else (j <= r)
        if kv_len is not None:
            seen = seen & (j < kv_len)
        if seen.any():
            walk.append((c, not seen.all()))
    return tuple(walk)


@pytest.mark.parametrize("rows,chunk,window", [
    (16, 16, 32), (32, 16, 48), (16, 32, 64), (64, 64, 64), (16, 16, 16),
    (512, 512, 4096)])
def test_the_band_walk_is_the_masks(rows, chunk, window):
    """``_key_walk`` under a window against the mask written out, every
    run of a square of 24 chunks; the tiles ``causal_walk`` counts are
    the walks' sum."""
    n = 24
    total = chunk * n
    walks = [att._key_walk(row0, rows, chunk, n, True, None, window)
             for row0 in range(0, total, rows)]
    for row0, walk in zip(range(0, total, rows), walks):
        assert walk == _brute_walk(row0, rows, chunk, n, window), row0
    walked, masked, pairs = att.causal_walk(total, total, rows, chunk,
                                            window=window)
    assert walked == sum(len(w) for w in walks)
    assert masked == sum(m for w in walks for _, m in w)
    assert pairs == len(walks) * n
    plain = att.causal_walk(total, total, rows, chunk)
    assert walked < plain[0] and plain[2] == pairs


def test_without_a_window_the_walks_are_the_tuples_they_were():
    """``gpt2m-train``'s walks: the causal tuples by hand, unchanged by
    the window's arithmetic, and the counts the records hold."""
    assert att._key_walk(512, 512, 512, 4, True) == (
        (0, False), (1, True))
    assert att._key_walk(0, 256, 256, 8, True) == ((0, True),)
    assert att._key_walk(1024, 512, 256, 8, True) == (
        (0, False), (1, False), (2, False), (3, False), (4, True),
        (5, True))
    assert att._key_walk(0, 64, 32, 5, False, 136) == tuple(
        (c, c == 4) for c in range(5))
    for row0 in range(0, 2048, 256):
        assert att._key_walk(row0, 256, 256, 8, True) \
            == _brute_walk(row0, 256, 256, 8, None)
    assert att.causal_walk(1024, 1024, 256, 256) == (10, 4, 16)
    assert att.causal_walk(2048, 2048, 256, 256) == (36, 8, 64)
    # the 12,288-token prompt of the cell, by tiles of 512: the band is
    # 60% of the causal walk's tiles (56% of its pairs)
    walked, masked, causal = att.band_tiles(12288, 128, 4096)
    assert (walked, masked, causal) == (180, 40, 300)
    assert att.band_tiles(4096, 128, 4096) == (36, 8, 36)
    with pytest.raises(NotImplementedError):
        att.causal_walk(1024, 1024, 256, 256, True, True, window=512)


@pytest.mark.parametrize("t,window,blocks", [
    (256, 64, (64, 128, 32, 32)), (200, 48, (64, 64, 16, 32)),
    (512, 96, (128, 256, 64, 64)), (96, 1, (32, 32, 16, 16))])
def test_the_banded_kernel_is_the_banded_softmax(t, window, blocks):
    """The flash forward under a window (the interpreter) against the
    exact softmax with the same mask and against the mask written out,
    where the band leaves blocks, runs and chunks at every offset."""
    rng = np.random.RandomState(t)
    q, k, v = (jnp.asarray(rng.standard_normal((1, 2, t, 16)), jnp.float32)
               for _ in range(3))
    ref = att._attention_fwd_ref(q, k, v, True, 0.3, window=window)
    got = att._flash_fwd_pallas(q, k, v, True, 0.3, interpret=True,
                                blocks=blocks, window=window)
    s = np.einsum("bhqd,bhkd->bhqk", np.asarray(q), np.asarray(k)) * 0.3
    i, j = np.arange(t)[:, None], np.arange(t)[None]
    s = np.where((j <= i) & (i - j < window), s, -np.inf)
    p = np.exp(s - s.max(-1, keepdims=True))
    want = (p / p.sum(-1, keepdims=True)) @ np.asarray(v)
    np.testing.assert_allclose(np.asarray(ref), want, atol=1e-5, rtol=0)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), atol=1e-5,
                               rtol=0)
    walked, masked, _ = att.causal_walk(t, t, blocks[2], blocks[3],
                                        window=window)
    assert att._M_WINDOW_TILES.labels("walked", str(t)).value == walked
    assert att._M_WINDOW_TILES.labels("masked", str(t)).value == masked


def test_a_window_no_shorter_than_the_prompt_is_the_causal_kernel():
    """``gqa_prefill_attention`` with a window at least the prompt's
    length is the plain causal call (under the window layers' scope);
    the backward walks refuse a window."""
    rng = np.random.RandomState(5)
    q = jnp.asarray(rng.standard_normal((1, 14, 40, 8)), jnp.float32)
    k, v = (jnp.asarray(rng.standard_normal((1, 2, 40, 8)), jnp.float32)
            for _ in range(2))
    plain = att.gqa_prefill_attention(q, k, v, 0.3)
    np.testing.assert_array_equal(
        att.gqa_prefill_attention(q, k, v, 0.3, window=40), plain)
    banded = att.gqa_prefill_attention(q, k, v, 0.3, window=8)
    assert np.abs(np.asarray(banded - plain))[:, :, 8:].max() > 1e-3
    np.testing.assert_allclose(banded[:, :, :8], plain[:, :, :8], atol=1e-6)
    text = jax.jit(lambda *a: att.gqa_prefill_attention(
        *a, 0.3, window=8)).lower(q, k, v).as_text(debug_info=True)
    assert "gqa_window_prefill_attention" in text
    with pytest.raises(NotImplementedError, match="forward"):
        att._flash_fwd_pallas(q[:, :2], q[:, :2], q[:, :2], True, 0.3,
                              interpret=True, return_lse=True, window=8)
    with pytest.raises(ValueError, match="causal"):
        att._key_walk(0, 16, 16, 4, False, None, 32)


# ----------------------------------------------------------------------
# (g) the expert layer: ReLU as an argument, the router ahead


def _dense_experts(x, chosen, gates, w_gate, w_up, w_down, first, act):
    y = np.zeros_like(x)
    for t in range(x.shape[0]):
        for e, g in zip(chosen[t], gates[t]):
            if first <= e < first + w_gate.shape[0]:
                w = e - first
                h = act(x[t] @ w_gate[w]) * (x[t] @ w_up[w])
                y[t] += g * (h @ w_down[w])
    return y


@pytest.mark.parametrize("every_row", [False, True],
                         ids=["grouped", "every-row"])
def test_dropless_experts_with_relu_is_the_dense_loop(every_row):
    rng = np.random.RandomState(0)
    x = rng.standard_normal((24, 32)).astype(np.float32)
    w_gate, w_up = (rng.standard_normal((4, 32, 16)).astype(np.float32) * .3
                    for _ in range(2))
    w_down = rng.standard_normal((4, 16, 32)).astype(np.float32) * .3
    chosen, gates = moe.route_softmax_topk(
        jnp.asarray(rng.standard_normal((24, 8)), jnp.float32), top_k=3)
    args = (jnp.asarray(x), chosen, gates, jnp.asarray(w_gate),
            jnp.asarray(w_up), jnp.asarray(w_down), (2, 4))
    got, counts = moe.dropless_experts(*args, every_row=every_row,
                                       activation="relu")
    want = _dense_experts(x, np.asarray(chosen), np.asarray(gates), w_gate,
                          w_up, w_down, 2, lambda a: np.maximum(a, 0))
    np.testing.assert_allclose(np.asarray(got), want, atol=2e-5, rtol=0)
    assert int(counts[0]) == 24 * 3
    # silu, named or not, is bit for bit what it was
    default = moe.dropless_experts(*args, every_row=every_row)[0]
    named = moe.dropless_experts(*args, every_row=every_row,
                                 activation="silu")[0]
    np.testing.assert_array_equal(np.asarray(default), np.asarray(named))
    silu = _dense_experts(x, np.asarray(chosen), np.asarray(gates), w_gate,
                          w_up, w_down, 2, lambda a: a / (1 + np.exp(-a)))
    np.testing.assert_allclose(np.asarray(default), silu, atol=2e-5, rtol=0)
    assert np.abs(np.asarray(default) - want).max() > 1e-2
    with pytest.raises(KeyError):
        moe.dropless_experts(*args, activation="gelu")


def test_silu_callers_trace_as_they_did():
    """The default leaves every other family's program as it is: the
    jaxpr of a call that does not name the activation is the jaxpr of
    one that names silu, and holds no relu (max)."""
    x = jnp.ones((8, 32))
    w = jnp.ones((4, 32, 16))
    chosen = jnp.zeros((8, 2), jnp.int32)
    gates = jnp.ones((8, 2))

    def call(**kw):
        return str(jax.make_jaxpr(lambda *a: moe.dropless_experts(
            *a, (0, 4), every_row=True, **kw))(
                x, chosen, gates, w, w, w.transpose(0, 2, 1)))

    assert call() == call(activation="silu")
    assert "logistic" in call() and "logistic" not in call(
        activation="relu")


# ----------------------------------------------------------------------
# (i) the configuration, its count and its bytes


def test_configuration_keeps_the_published_widths():
    cfg = _published()
    want = {"hidden_size": 2560, "num_attention_heads": 28,
            "num_key_value_heads": 4, "head_dim": 128,
            "moe_ffn_hidden_size": 768, "moe_num_active_primary_experts": 6,
            "sliding_window_size": 4096, "rope_theta": 1500000,
            "max_position_embeddings": 16384, "rms_norm_eps": 1e-6,
            "norm_topk_prob": True, "tie_word_embeddings": False,
            "moe_primary_router_apply_softmax": True, "rope_scaling": None}
    assert {k: cfg[k] for k in want} == want
    assert cfg["reduced"] == ["num_hidden_layers", "moe_num_primary_experts",
                              "vocab_size"]
    assert cfg["published"] == {"num_hidden_layers": 52,
                                "moe_num_primary_experts": 64,
                                "vocab_size": 151936}
    assert (cfg["num_hidden_layers"], cfg["moe_num_primary_experts"],
            cfg["vocab_size"]) == (16, 16, 37984)
    assert set(cfg["reduced_why"]) == set(cfg["reduced"])
    for key in ("sliding_window_layout", "rope_layout"):
        assert cfg[key] == [0, 1, 1, 1] * 13          # whole, as published
    dep = cfg["deployment"]
    assert dep["chips_sharing_a_layer"] == 4 and dep["vocab_shards"] == 4
    assert dep["experts"] == {"published": 64, "held": 16, "first": 0}
    assert dep["serve"]["checked_logit_parts"] == 8
    assert cfg["vocab_size"] % 8 == 0 and cfg["n_positions"] == 16384
    assert len(dep["serve"]["num_blocks"]) == 2
    # the catalog's row, key for key but for the three reduced
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.exists(catalog):
        with open(catalog) as f:
            row = next(r for r in map(json.loads, f)
                       if r["name"] == "SmallThinker-21BA3B-Instruct")
        differ = {k for k, v in row["config"].items() if cfg.get(k) != v}
        assert differ == set(cfg["reduced"])
        assert cfg["source"] == row["source_url"]
    program = _family().program_config(cfg)
    assert program["held"] == (0, 16) and program["num_experts"] == 64
    assert program["layer_windows"] == (False, True, True, True) * 4
    assert wm.cache_groups(program) == (
        ((0, 1, 2, 3), None), (tuple(range(4, 16)), 4096))


def test_parameter_count_of_the_cut_and_of_the_published_model():
    """2,043M held here and 21,507M published, from the shapes."""
    family = _family()
    cfg = _published()
    held = sum(int(np.prod(s)) for s in family.weight_shapes(cfg).values())
    assert round(held / 1e6) == 2043
    assert abs(held * 2 / 1e9 - 4.09) < 0.01
    whole = dict(cfg, num_hidden_layers=52, moe_num_primary_experts=64,
                 vocab_size=151936)
    whole["deployment"] = {"experts": {"published": 64, "held": 64,
                                       "first": 0}}
    published = sum(int(np.prod(s))
                    for s in family.weight_shapes(whole).values())
    assert round(published / 1e6) == 21507
    # a layer outside its experts, an expert, and the active parameters
    shapes = family.weight_shapes(cfg)
    layer = sum(int(np.prod(s)) for k, s in shapes.items()
                if k.startswith("l3_") and "experts" not in k)
    assert round(layer / 1e4) == 2114
    expert = 3 * 2560 * 768
    assert expert == 5898240
    active = 52 * (layer + 6 * expert) + 151936 * 2560     # and the head
    assert 3.3e9 < active < 3.4e9                      # "A3B"
    # a chip of the four-chip host: every layer, 16 experts, a quarter
    # of embedding and head
    chip = 52 * (layer + 16 * expert) + 2 * 37984 * 2560
    assert round(chip / 1e6) == 6201


def test_a_cached_token_is_8_kb_global_and_24_kb_in_the_ring():
    cfg = _published()
    definition = wm.lm_definition(_family().program_config(cfg))
    assert definition.cache_row.bytes == 2048 and definition.state is None
    (whole, _), (ring, window) = definition.cache_groups
    assert len(whole) * 2048 == 8192 and len(ring) * 2048 == 24576
    assert (window // 16 + 1) * 16 * len(ring) * 2048 == 101056512  # 101 MB
    serve = cfg["deployment"]["serve"]
    pools = [blocks * 16 * len(layers) * 2048 for blocks, (layers, _)
             in zip(serve["num_blocks"], definition.cache_groups)]
    assert [round(p / 1e7) for p in pools] == [265, 473]
    # with the weights 11.47 GB: the 12,288 bucket's 1.9 GB of
    # temporaries and outputs and the 1.4 GB the device reserves then
    # stay under the 14.5 GB the issue allows
    assert 4.09e9 + sum(pools) < 11.5e9


def test_seeded_routing_spreads_over_the_experts():
    """How evenly the softmax router spreads 6 of 64 under the seeded
    weights (normal(0, 0.02) over 2560, unit-RMS rows): what the
    configuration's ``assumed`` says."""
    shares, gates, reached = [], [], []
    for seed in range(3):
        key = jax.random.PRNGKey(seed)
        w = 0.02 * jax.random.normal(key, (64, 2560), jnp.float32)
        h = jax.random.normal(jax.random.fold_in(key, 1), (4096, 2560))
        chosen, g = moe.route_softmax_topk(h @ w.T, top_k=6)
        count = np.bincount(np.asarray(chosen).ravel(), minlength=64)
        shares.append(count / (4096 * 6 / 64.0))
        gates.append(np.asarray(g))
        hit = [len(set(np.asarray(chosen)[i:i + 48][
            np.asarray(chosen)[i:i + 48] < 16].tolist())) == 16
            for i in range(0, 4096 - 48, 48)]
        reached.append(np.mean(hit))
    shares, gates = np.stack(shares), np.concatenate(gates)
    assert 0.8 < shares.min() and shares.max() < 1.25
    # a token's six gates run from 0.29 down to 0.11 on average
    assert 0.05 < np.percentile(gates, 1) and np.percentile(gates, 99) < 0.5
    assert 0.25 < gates[:, 0].mean() < 0.33 and 0.09 < gates[:, -1].mean()
    assert min(reached) > 0.85


def test_cost_arithmetic():
    from benchmark import flops, latent_moe_costs, window_moe_costs

    cfg = _published()
    assert latent_moe_costs.expert_weight_bytes(cfg) == 11796480
    assert window_moe_costs.band_pairs(4096, 4096) == 4096 * 4097 // 2
    assert window_moe_costs.band_pairs(100, 4096) == 5050
    # the issue's 12,288-token prompt: 41.9M pairs of the triangle's 75.5M
    assert window_moe_costs.band_pairs(12288, 4096) == 41945088
    assert 12288 * 12289 // 2 == 75503616
    brute = sum(min(r + 1, 32) for r in range(100))
    tiny = dict(cfg, sliding_window_size=32)
    ops, moved = window_moe_costs.band_prefill_cost(tiny, 100)
    assert ops == 4 * 28 * 128 * brute
    assert moved == (2 * 28 + 2 * 4) * 100 * 128 * 2
    peaks = {"flops_per_s": {"bfloat16": 197e12}, "hbm_bytes_per_s": 819e9}
    ops, moved = window_moe_costs.band_prefill_cost(cfg, 12288)
    assert flops.roofline_seconds(ops, moved, peaks)[1] == "compute"
    # 48 rows at the window, one layer: 2 KB a key
    ops, moved = window_moe_costs.window_decode_cost(cfg, 48 * 4096, 48)
    assert moved == (48 * 4096 * 2 * 512 + 48 * 28 * 2 * 128) * 2
    least, by = flops.roofline_seconds(ops, moved, peaks)
    assert by == "memory" and abs(least - 0.492e-3) < 0.002e-3


# ----------------------------------------------------------------------
# (j) the readers of the new metrics


_NEW_METRICS = (
    "moe_expert_share.smallthinker", "moe_expert_roofline.smallthinker",
    "moe_tokens_per_held_expert.smallthinker",
    "moe_held_experts_hit_share.smallthinker",
    "window_decode_attn_share.smallthinker",
    "gqa_paged_decode_roofline.smallthinker",
    "window_paged_decode_roofline.smallthinker",
    "window_prefill_roofline.smallthinker",
    "window_prefill_attn_share.smallthinker",
    "window_keys_walked_share.smallthinker",
    "window_kv_occupancy_peak.smallthinker")


def _trace(events):
    end = max(at + dur for _, at, dur in events)
    return {"window_ns": [0, end], "devices": {"0": events}, "host": []}


def _recorded_events():
    """(name, nanoseconds) of a decode step's and a prefill's operations
    as a traced run of the cell names them (recorded on the chip)."""
    with open(os.path.join(ROOT, "benchmark", "data",
                           "smallthinker_trace_names.json")) as f:
        return [(e["name"], e["ns"]) for e in json.load(f)["events"]]


def test_readers_of_the_new_metrics(capsys):
    """On the recorded names of one decode step and one long prefill:
    the shares count what their patterns name, the rooflines come out
    under 100% and say which peak bounds them, and every reader returns
    nothing where there is nothing to read (the parent's program: no
    such counter, no such operation, no such gauge)."""
    from benchmark.spec import Spec
    from mxnet_tpu.ops import kv_cache

    spec = Spec(ROOT)
    peaks = spec.peaks("TPU v5 lite")

    def read(metric, ctx):
        doc = spec.metric_file(metric)
        return spec.reader(doc["reader"])(ctx, doc.get("params", {}))

    events, at = [], 0
    for name, dur in _recorded_events():
        events.append([name, at, dur])
        at += dur + 1000
    steps = 100.0
    counters = {"generation_decode_steps_total": steps,
                "generation_decode_context_tokens_total": steps * 48 * 4500,
                "generation_decode_window_tokens_total": steps * 48 * 3000,
                "generation_tokens_total": steps * 48,
                "moe_layer_steps_total": 1600.0,
                "moe_local_experts_hit_total": 1600 * 16.0,
                "moe_local_assignments_total": 1600 * 72.0}
    ctx = {"trace": _trace(events), "peaks": peaks,
           "compiles_in_window": counters}
    bare = {"trace": _trace([["%fusion.1 = f32[8,8] fusion(%p)", 0, 50]]),
            "peaks": peaks, "compiles_in_window": {
                "generation_decode_steps_total": 100.0}}
    # the parent's program first (no such counter, no such operation;
    # the registry has no such gauge until a cell of this model ran)
    gauge = "window_kv_occupancy_peak.smallthinker"
    had = read(gauge, bare) is not None
    for name in _NEW_METRICS:
        if not (had and name == gauge):
            assert read(name, bare) is None, name
            assert read(name, {"peaks": peaks}) is None, name
    kv_cache._M_GROUP_PEAK.labels("bench_lm", "1").set(0.8125)
    kv_cache._M_GROUP_PEAK.labels("bench_lm", "0").set(0.5)
    got = {m: read(m, ctx) for m in _NEW_METRICS}
    out = capsys.readouterr().out
    assert "expert roofline: bound by memory" in out
    assert "gqa decode roofline: bound by memory" in out
    assert "window decode roofline: bound by memory" in out
    assert "band prefill roofline: kernels by prompt length" in out
    assert got["moe_tokens_per_held_expert.smallthinker"] == 4.5
    assert got["moe_held_experts_hit_share.smallthinker"] == 100.0
    assert got["window_keys_walked_share.smallthinker"] \
        == pytest.approx(100 * 3000 / 4500.0)
    assert got["window_kv_occupancy_peak.smallthinker"] == 81.25
    for name in ("moe_expert_share.smallthinker",
                 "window_decode_attn_share.smallthinker",
                 "window_prefill_attn_share.smallthinker"):
        assert 0 < got[name] < 100, (name, got[name])
    for name in ("gqa_paged_decode_roofline.smallthinker",
                 "window_paged_decode_roofline.smallthinker",
                 "window_prefill_roofline.smallthinker",
                 "moe_expert_roofline.smallthinker"):
        assert 0 < got[name] <= 100, (name, got[name])
