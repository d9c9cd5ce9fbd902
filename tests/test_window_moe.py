"""The decoder of sliding-window and global grouped-query attention over
ReGLU experts (``models/window_moe.py``) and what it forced: a cache of
two **layer groups** with a pool, a free list and a table each, in which
a window layer's blocks are a ring; the decode walk over that ring with
seven query heads a key-value head; the flash kernel over a band; a
router that reads the attention's input; the expert layer's activation
as an argument.

Everything is held against the benchmark's plain reference
(``benchmark/configs/smallthinker-21b-ep4.reference.py``, which imports
nothing of the program) at a tiny size with the published *structure*:
the first five layers of the two published lists of period four (global
without positions, three window layers with rotary, global again: two
cached layers in the first group and three in the second), 14 query
heads over 2 key-value heads (seven a head), 8 experts of which 3 a
token, and a **window of 32 tokens over blocks of 16**, so that a ring
is 3 blocks and a sequence of 120 tokens wraps it twice and more.
float32 on the CPU, so the two sides differ by the order of float32
additions only.
"""

import copy
import json
import os
import shutil

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from mxnet_tpu import serving
from mxnet_tpu.models import window_moe as wm
from mxnet_tpu.ops import attention as att
from mxnet_tpu.ops import paged_attention as paged
from mxnet_tpu.ops.kv_cache import (CacheExhaustedError, CacheRow,
                                    PagedKVCache)
from mxnet_tpu.parallel import moe

from test_gated_delta_moe import _counter

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CONFIG = os.path.join(ROOT, "benchmark", "configs",
                      "smallthinker-21b-ep4.json")
REFERENCE = CONFIG[:-len(".json")] + ".reference.py"
LAYOUT = [0, 1, 1, 1, 0, 1, 1, 1]
# the benchmark's configuration file at the tiny size: the published
# keys, the experts held (all 8 here), the deployment
TINY = {
    "family": "window_moe", "hidden_size": 32, "num_hidden_layers": 5,
    "num_attention_heads": 14, "num_key_value_heads": 2, "head_dim": 8,
    "moe_ffn_hidden_size": 16, "moe_intermediate_size": 16,
    "moe_num_primary_experts": 8, "moe_num_active_primary_experts": 3,
    "moe_primary_router_apply_softmax": True, "norm_topk_prob": True,
    "rms_norm_eps": 1e-6, "rope_theta": 1500000, "rope_scaling": None,
    "sliding_window_size": 32, "sliding_window_layout": LAYOUT,
    "rope_layout": LAYOUT, "vocab_size": 50, "n_positions": 128,
    "deployment": {"experts": {"published": 8, "held": 8, "first": 0},
                   "serve": {"dtype": "float32", "block_size": 16,
                             "num_blocks": [32, 16]}}}
# 0.3-wide weights: large enough that the experts, the rotary, the
# gates and the window all move the logits
SCALE = 0.3
# what the two float32 sides may differ by, on logits of size ~7
TOL = 2e-4


def held_config(first=0, count=8):
    cfg = copy.deepcopy(TINY)
    cfg["moe_num_primary_experts"] = count
    cfg["deployment"]["experts"].update(held=count, first=first)
    return cfg


def program_config(cfg):
    share = cfg["deployment"]["experts"]
    return wm.lm_config(
        dict(cfg, moe_num_primary_experts=share["published"]),
        seq_len=cfg["n_positions"], held=(share["first"], share["held"]))


@pytest.fixture(scope="module")
def reference():
    from benchmark.spec import load_module

    return load_module(REFERENCE, "reference_smallthinker")


@pytest.fixture(scope="module")
def model():
    cfg = program_config(TINY)
    return cfg, wm.init_params(cfg, 0, jnp.float32, SCALE)


def _tokens(n, seed=0):
    return np.random.RandomState(seed).randint(
        0, TINY["vocab_size"], n).astype(np.int32)


def _backend(model, name, num_blocks=(32, 16)):
    cfg, params = model
    return serving.LMBackend(
        params, definition=wm.lm_definition(cfg, jnp.float32),
        block_size=16, num_blocks=list(num_blocks), model=name)


def _prefill(be, seq, tokens, bucket):
    padded = np.zeros(bucket, np.int32)
    padded[:len(tokens)] = tokens
    logits, k, v, _ = be.prefill(padded, len(tokens))
    be.cache.write_prefill(seq, k, v, len(tokens))
    return logits


def _step(be, seq, token, position, run_ahead=False, table=None):
    if table is None:
        table = be.cache.block_table(seq, be.max_blocks_per_seq)[None]
    be.run_ahead = run_ahead
    try:
        return be.decode([token], [position], table, [position + 1])[0][0]
    finally:
        be.run_ahead = False


def _reference_logits(reference, params, toks, tiny=TINY):
    return np.asarray(reference.logits(
        tiny, params, np.asarray(toks, np.int32)[None]))[0]


# ----------------------------------------------------------------------
# (a) the full forward, (b) prefill then decode through both pools


def test_full_forward_is_the_reference_on_a_share(reference):
    """With a share of the experts held (ids 2-5 of 8): the reference
    leaves out what the absent four would add, as the program does.  40
    tokens: the window layers' band has left the triangle."""
    tiny = held_config(first=2, count=4)
    cfg = program_config(tiny)
    assert cfg["held"] == (2, 4) and cfg["num_experts"] == 8
    assert cfg["layer_windows"] == (False, True, True, True, False)
    assert cfg["layer_rotary"] == cfg["layer_windows"]
    params = wm.init_params(cfg, 1, jnp.float32, SCALE)
    assert params["l2_experts_gate_weight"].shape == (4, 32, 16)
    assert params["l0_router_weight"].shape == (8, 32)
    assert params["pred_weight"].shape == params["embed_weight"].shape
    toks = _tokens(40, 3)
    want = _reference_logits(reference, params, toks, tiny)
    got = np.asarray(wm.full_logits(params, toks[None], cfg))[0]
    assert np.abs(want).max() > 1.0
    np.testing.assert_allclose(got, want, atol=TOL, rtol=0)


def test_a_global_layer_has_no_position_and_a_window_layer_a_band(
        reference):
    """What the two lists mean, on the reference: with one global layer
    alone, two prompts that differ in the order of their first tokens
    give the same last logits (no position enters but through the mask);
    with one window layer alone, the last logits do not depend on a
    token that has left the window and do on the oldest one inside."""
    def one_layer(kind):
        tiny = copy.deepcopy(TINY)
        tiny.update(num_hidden_layers=1, sliding_window_layout=[kind] * 8,
                    rope_layout=[kind] * 8)
        cfg = program_config(tiny)
        return tiny, cfg, wm.init_params(cfg, 2, jnp.float32, SCALE)

    toks = _tokens(40, 5)
    swapped = toks.copy()
    swapped[[0, 1]] = toks[[1, 0]]
    tiny, cfg, params = one_layer(0)
    for logits in (lambda t: _reference_logits(reference, params, t, tiny),
                   lambda t: np.asarray(
                       wm.full_logits(params, t[None], cfg))[0]):
        np.testing.assert_allclose(logits(toks)[-1], logits(swapped)[-1],
                                   atol=1e-5, rtol=0)
    tiny, cfg, params = one_layer(1)
    changed = toks.copy()
    changed[39 - 32] = (toks[39 - 32] + 1) % 50       # just left the window
    inside = toks.copy()
    inside[39 - 31] = (toks[39 - 31] + 1) % 50        # the oldest key seen
    for logits in (lambda t: _reference_logits(reference, params, t, tiny),
                   lambda t: np.asarray(
                       wm.full_logits(params, t[None], cfg))[0]):
        base = logits(toks)[-1]
        np.testing.assert_array_equal(logits(changed)[-1], base)
        assert np.abs(logits(inside)[-1] - base).max() > 1e-3


@pytest.mark.parametrize("run_ahead", [False, True], ids=["alone", "ahead"])
@pytest.mark.parametrize("prompt,bucket", [(5, 8), (37, 64), (52, 64)])
def test_prefill_then_decode_through_both_pools_is_the_reference(
        model, reference, prompt, bucket, run_ahead):
    """A prompt under the window, one over it and one over the ring, at
    a padded bucket, then greedy decode steps to token 102 through
    ``LMBackend``: the global layers through their pool, the window
    layers through the ring of 3 blocks, which the sequence wraps more
    than twice.  Every step's logits against the reference's one
    forward over all 102 tokens; with run-ahead every call but the
    first is answered by the step queued behind the one before it."""
    name = "wm_%d%d" % (prompt, run_ahead)
    be = _backend(model, name)
    # two cached layers in the global group, three in the window group
    assert be.cache.k_pages.shape == (2, 32, 16, 16)
    pools = be.cache.program_pools()
    assert [p.shape for p in pools[0]] == [(2, 32, 16, 16), (3, 16, 16, 16)]
    assert be.max_blocks_per_seq == 8 + 3 and be.windows == (32,)
    toks = list(_tokens(prompt, 7))
    be.cache.allocate("s", 120)
    groups = be.cache.stats()["groups"]
    assert [g["used"] for g in groups] == [8, 3]
    got = [_prefill(be, "s", toks, bucket)]
    for t in range(prompt, 102):
        toks.append(int(np.argmax(got[-1])))
        got.append(_step(be, "s", toks[-1], t, run_ahead and t < 101))
    want = _reference_logits(reference, model[1], toks)
    np.testing.assert_allclose(np.stack(got), want[prompt - 1:], atol=TOL,
                               rtol=0)
    used = _counter("generation_decode_ahead_used_total", model=name)
    assert used == (101 - prompt if run_ahead else 0)
    # a step that begins a block past the ring's third writes over an
    # entry: the blocks 3..6 of the sequence that a decode step began,
    # each position written once, queued ahead or not
    assert _counter("serving_kv_cache_ring_wraps_total", model=name) \
        == len([b for b in range(3, 7) if b * 16 >= prompt])
    assert [g["used"] for g in be.cache.stats()["groups"]] == [8, 3]
    assert _counter("kv_cache_layers", model=name) == 5
    assert _counter("serving_kv_cache_group_used_blocks", model=name,
                    group="1") == 3
    be.cache.free("s")
    assert [g["used"] for g in be.cache.stats()["groups"]] == [0, 0]


def test_a_long_sequence_holds_its_ring_and_all_its_global_blocks(model):
    """At the published sizes, from shapes alone (no pool is built): a
    sequence of more than 4,112 tokens holds 257 blocks in the window
    group and ``ceil(tokens / 16)`` in the global one; one under the
    window costs either group the same."""
    from mxnet_tpu.ops.kv_cache import _Group

    ring = _Group(range(4, 16), 4096, 12032, 16, 16384)
    whole = _Group(range(4), None, 20224, 16, 16384)
    assert (whole.width, ring.width, ring.ring) == (1024, 257, 257)
    for tokens in (4113, 9000, 14336):
        assert ring.blocks_for(tokens, 16) == 257
        assert whole.blocks_for(tokens, 16) == -(-tokens // 16)
    assert ring.blocks_for(4112, 16) == 257
    assert ring.blocks_for(4096, 16) == 256 == whole.blocks_for(4096, 16)
    assert ring.blocks_for(700, 16) == 44 == whole.blocks_for(700, 16)
    # token p lies in entry (p // 16) mod 257
    at = np.array([0, 15, 16, 4111, 4112, 4113, 14335])
    assert ring.entry(at, 16).tolist() == [0, 0, 1, 256, 0, 0, 124]
    assert whole.entry(at, 16).tolist() == [0, 0, 1, 256, 257, 257, 895]


# ----------------------------------------------------------------------
# (c) the cache's layer groups


def _cache(model="wm_cache", **kw):
    kw.setdefault("num_blocks", [12, 6])
    return PagedKVCache(
        num_layers=5, row=CacheRow("kv", 16, np.float32, 2), block_size=16,
        model=model, groups=(((0, 1), None), ((2, 3, 4), 32)),
        max_tokens=128, **kw)


def test_allocation_429_on_either_pool_and_free_returns_both():
    cache = _cache()
    assert cache.table_width == 8 + 3 and cache.num_blocks == 12
    cache.allocate("a", 100)              # 7 global blocks, a ring of 3
    cache.allocate("b", 20)               # 2 and 2: under the window
    assert [g["used"] for g in cache.stats()["groups"]] == [9, 5]
    row = cache.block_table("a", 11)
    assert (row[:7] > 0).sum() >= 6 and row[7] == 0 and len(set(row[8:])) == 3
    with pytest.raises(CacheExhaustedError) as err:
        cache.allocate("c", 40)           # 3 global fit, a ring of 3 not
    assert "layer group 1" in str(err.value)
    assert err.value.http_status == 429
    assert err.value.kv_cache_blocks_total == 6
    # nothing was taken from the pool that had room
    assert [g["used"] for g in cache.stats()["groups"]] == [9, 5]
    with pytest.raises(CacheExhaustedError) as err:
        cache.allocate("d", 64)           # 4 global of the 3 left
    assert "layer group 0" in str(err.value)
    assert cache.stats()["occupancy"] == 9 / 12.0      # the global pool's
    assert cache.stats()["groups"][1]["peak"] == 5 / 6.0
    assert len(cache.free("a")) == 7
    assert [g["used"] for g in cache.stats()["groups"]] == [2, 2]
    cache.allocate("c", 40)
    assert sorted(cache.sequences()) == ["b", "c"]
    with pytest.raises(Exception, match="11 wide"):
        cache.block_table("b", 8)


def test_a_one_group_cache_is_the_cache_it_was():
    """No groups named: one pool over every layer, one table of any
    width, the stats and the programs' pools as they were."""
    cache = PagedKVCache(num_layers=3, num_heads=2, head_dim=4,
                         block_size=4, num_blocks=8, model="wm_one")
    assert cache.k_pages.shape == (3, 8, 4, 8) == cache.v_pages.shape
    k, v = cache.program_pools()
    assert k is cache.k_pages and v is cache.v_pages
    cache.allocate("s", 9)
    assert cache.block_table("s", 5).tolist() == [0, 1, 2, 0, 0]
    assert cache.block_table("s", 3).tolist() == [0, 1, 2]
    stats = cache.stats()
    assert (stats["blocks"], stats["used"], stats["free"]) == (8, 3, 5)
    assert stats["occupancy"] == 0.375 and stats["sequences"] == 1
    assert stats["groups"] == [{"layers": 3, "window": None, "blocks": 8,
                                "used": 3, "occupancy": 0.375,
                                "peak": 0.375}]
    rows = jnp.ones((3, 2, 8))
    cache.write_tokens(np.array([[0, 1, 2], [0, 0, 0]], np.int32),
                       np.array([5, 0], np.int32), rows, rows)
    assert float(cache.k_pages[:, 1, 1].sum()) == 24.0
    assert float(cache.k_pages.sum()) == 24.0
    assert cache.free("s") == [0, 1, 2]
    # the per-group gauges are a model's with several groups
    assert _counter("serving_kv_cache_group_used_blocks", model="wm_one",
                    group="0") == 0
    assert _counter("serving_kv_cache_used_blocks", model="wm_one") == 0


def test_a_prefill_writes_a_ring_its_last_blocks():
    """A prompt of 53 tokens into a ring of 3 blocks: the window group
    takes blocks 1-3 of the prompt (tokens 16-52), block 3 lying in
    entry 0; the global group takes all four."""
    cache = _cache("wm_prefill")
    cache.allocate("s", 100)
    k = jnp.broadcast_to(jnp.arange(64, dtype=jnp.float32)[None, :, None],
                         (5, 64, 16)) + 1
    cache.write_prefill("s", k, k, 53)
    pools = cache.program_pools()[0]
    row = cache.block_table("s", 11)
    whole = np.asarray(pools[0])[0][row[:4]].reshape(64, 16)[:, 0]
    assert whole[:53].tolist() == list(range(1, 54)) and not whole[53:].any()
    ring = np.asarray(pools[1])[0][row[8:]][:, :, 0]
    assert ring[1].tolist() == list(range(17, 33))
    assert ring[2].tolist() == list(range(33, 49))
    assert ring[0].tolist() == list(range(49, 54)) + [0] * 11
    assert cache.length("s") == 53


# ----------------------------------------------------------------------
# (d) retries and run-ahead over a wrapped ring


def test_a_repeated_step_over_a_wrapped_ring_changes_nothing(model,
                                                              reference):
    """A decode step that is dispatched again (a retry) rewrites the
    same ring slot with the same values, also where that slot is one
    the ring has wrapped onto."""
    be = _backend(model, "wm_retry")
    toks = list(_tokens(45, 9))
    be.cache.allocate("s", 120)
    got = [_prefill(be, "s", toks, 64)]
    for t in range(45, 70):
        toks.append(int(np.argmax(got[-1])))
        first = _step(be, "s", toks[-1], t)
        if t in (48, 63, 64):             # a block's first and last token
            again = _step(be, "s", toks[-1], t)
            np.testing.assert_array_equal(first, again)
        got.append(first)
    want = _reference_logits(reference, model[1], toks)
    np.testing.assert_allclose(np.stack(got), want[44:], atol=TOL, rtol=0)


def test_a_dropped_queued_step_over_a_wrapped_ring_changes_nothing(
        model, reference):
    """A step queued ahead and thrown away has written the ring slot of
    the next position, which the sequence writes again with the same
    values when it gets there: the block a step writes never holds a
    key that step, or one dispatched again behind it, still reads."""
    be = _backend(model, "wm_drop")
    toks = list(_tokens(45, 11))
    be.cache.allocate("s", 120)
    got = [_prefill(be, "s", toks, 64)]
    for t in range(45, 84):
        toks.append(int(np.argmax(got[-1])))
        got.append(_step(be, "s", toks[-1], t, run_ahead=t % 3 == 0))
        if t % 6 == 0:
            be.drop_ahead()
    want = _reference_logits(reference, model[1], toks)
    np.testing.assert_allclose(np.stack(got), want[44:], atol=TOL, rtol=0)
    assert _counter("generation_decode_ahead_dropped_total",
                    model="wm_drop") >= 6


def test_two_sequences_keep_their_own_rings_in_one_batch(model, reference):
    """Two sequences of different lengths in one decode batch, one far
    past its ring's wrap and one under the window, with a pad row: each
    row's logits are its own sequence's."""
    be = _backend(model, "wm_batch")
    a, b = list(_tokens(50, 13)), list(_tokens(6, 14))
    be.cache.allocate("a", 100)
    be.cache.allocate("b", 30)
    la, lb = _prefill(be, "a", a, 64), _prefill(be, "b", b, 8)
    got_a, got_b = [la], [lb]
    for step in range(20):
        a.append(int(np.argmax(got_a[-1])))
        b.append(int(np.argmax(got_b[-1])))
        tables = np.stack([be.cache.block_table("a", 11),
                           be.cache.block_table("b", 11),
                           np.zeros(11, np.int32)])
        pa, pb = 50 + step, 6 + step
        out = be.decode([a[-1], b[-1], 0], [pa, pb, 0], tables,
                        [pa + 1, pb + 1, 1])[0]
        got_a.append(out[0])
        got_b.append(out[1])
    np.testing.assert_allclose(
        np.stack(got_a), _reference_logits(reference, model[1], a)[49:],
        atol=TOL, rtol=0)
    np.testing.assert_allclose(
        np.stack(got_b), _reference_logits(reference, model[1], b)[5:],
        atol=TOL, rtol=0)


def test_the_loop_counts_what_the_window_saves(model):
    """Through ``GenerationScheduler``: the window counter is the
    smaller of context and window a row and step, the context counter
    the whole context; a sequence's table row is made once."""
    be = _backend(model, "wm_loop")
    sched = serving.GenerationScheduler(name="wm_sched")
    try:
        sched.register("wm_loop", be, decode_buckets=[2],
                       prefill_buckets=[16, 64])
        prompt = _tokens(40, 17).tolist()
        out = sched.generate("wm_loop", prompt, max_new_tokens=30)
        assert len(out) == 30
    finally:
        sched.close()
    steps = _counter("generation_decode_steps_total", model="wm_loop")
    assert steps == 29
    context = _counter("generation_decode_context_tokens_total",
                       model="wm_loop")
    assert context == sum(range(41, 70))
    assert _counter("generation_decode_window_tokens_total",
                    model="wm_loop") == 29 * 32
    assert _counter("generation_block_table_rows_built_total",
                    model="wm_loop") == 1
    # a prompt of 40 at a bucket of 64: 3 window layers' tiles
    walked = _counter("window_prefill_tiles_walked_total", model="wm_loop")
    causal = _counter("window_prefill_tiles_causal_total", model="wm_loop")
    assert 0 < walked <= causal
    assert _counter("moe_layer_steps_total", model="wm_loop") == 5 * 30
    assert be.cache.stats()["groups"][1]["peak"] == 3 / 16.0


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


def test_the_router_reads_the_attentions_input(model, reference):
    """The choice is made from ``N_in(x)``, before the attention, and
    the experts are applied to the attention's output path: the
    reference's choice at layer 0, computed by hand from the embedding,
    is what the program's layer counts, and a program that routed from
    the post-attention norm would choose otherwise for some token."""
    cfg, params = model
    toks = _tokens(40, 19)
    x = params["embed_weight"][toks]
    h = wm._lm._norm(x, params["l0_input_norm_gamma"], cfg)
    chosen, gates = wm._route(params, "l0_", h, cfg)
    want_chosen, want_gates = reference.route(
        TINY, jnp.einsum("tc,ec->te", h, params["l0_router_weight"]))
    np.testing.assert_array_equal(np.sort(np.asarray(chosen), -1),
                                  np.sort(np.asarray(want_chosen), -1))
    np.testing.assert_allclose(np.sort(np.asarray(gates), -1),
                               np.sort(np.asarray(want_gates), -1),
                               atol=1e-6)
    np.testing.assert_allclose(np.asarray(gates).sum(-1), 1.0, atol=1e-6)
    update, _, _ = wm._attention_prefill(
        params, 0, h, jnp.arange(40, dtype=jnp.int32), cfg)
    h2 = wm._lm._norm(x + update, params["l0_post_norm_gamma"], cfg)
    late = wm._route(params, "l0_", h2, cfg)[0]
    assert (np.sort(np.asarray(late), -1)
            != np.sort(np.asarray(chosen), -1)).any()


@pytest.mark.parametrize("holders", [1, 2, 4])
def test_the_shares_add_up_to_the_uncut_layer(model, reference, holders):
    """Guide section 4: the shares of the experts, each computed by a
    program that holds ``8 / holders`` of them, with the attention
    counted once, add up to the uncut layer of the reference."""
    cfg, params = model
    toks = _tokens(36, 21)
    x = params["embed_weight"][toks]
    h = wm._lm._norm(x, params["l1_input_norm_gamma"], cfg)
    chosen, gates = wm._route(params, "l1_", h, cfg)
    update, _, _ = wm._attention_prefill(
        params, 1, h, jnp.arange(36, dtype=jnp.int32), cfg)
    after = x + update
    count = 8 // holders
    total = after
    for i in range(holders):
        part = dict(params)
        for name in ("gate", "up", "down"):
            key = "l1_experts_%s_weight" % name
            part[key] = params[key][i * count:(i + 1) * count]
        share = dict(cfg, held=(i * count, count))
        total = total + wm._experts(part, 1, after, chosen, gates, share)[0]
    whole = wm._layer(params, 1, x, lambda hh: wm._attention_prefill(
        params, 1, hh, jnp.arange(36, dtype=jnp.int32), cfg), cfg)[0]
    np.testing.assert_allclose(np.asarray(total), np.asarray(whole),
                               atol=2e-5, rtol=0)
    # and the uncut layer is the reference's: one layer, all experts
    tiny = dict(copy.deepcopy(TINY), num_hidden_layers=2)
    ref_hidden = reference.hidden(tiny, params, jnp.asarray(toks))
    two = wm._layer(params, 1, wm._layer(
        params, 0, x, lambda hh: wm._attention_prefill(
            params, 0, hh, jnp.arange(36, dtype=jnp.int32), cfg), cfg)[0],
        lambda hh: wm._attention_prefill(
            params, 1, hh, jnp.arange(36, dtype=jnp.int32), cfg), cfg)[0]
    got = wm._lm._norm(two, params["final_norm_gamma"], cfg)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref_hidden),
                               atol=2e-5, rtol=0)


# ----------------------------------------------------------------------
# (h) both faults of the builder's chip runs fail the tiny limits


def _served_errors(model, reference, fault, monkeypatch, prompt):
    """Largest |program - reference| a token over ``prompt`` tokens and
    30 decode steps, with ``fault`` put into the program."""
    name = "wm_fault_%s" % fault
    if fault == "no-band":
        # a window layer's prefill without the band: plain causal
        real = att.gqa_prefill_attention
        monkeypatch.setattr(
            wm, "gqa_prefill_attention",
            lambda q, k, v, scale, window=None: real(q, k, v, scale))
    be = _backend(model, name)
    if fault == "ring-off-by-one":
        # the ring written one entry off after its first wrap
        ring = be.cache._groups[1]
        real_entry = ring.entry

        def entry(positions, block_size):
            index = positions // block_size
            return np.where(index >= ring.ring, (index + 1) % ring.ring,
                            real_entry(positions, block_size))
        ring.entry = entry
    toks = list(_tokens(prompt, 23))
    be.cache.allocate("s", 120)
    got = [_prefill(be, "s", toks, 64)]
    for t in range(prompt, prompt + 30):
        toks.append(int(np.argmax(got[-1])))
        got.append(_step(be, "s", toks[-1], t))
    want = _reference_logits(reference, model[1], toks)
    return np.abs(np.stack(got) - want[prompt - 1:]).max(axis=1)


@pytest.mark.parametrize("fault", ["sound", "no-band", "ring-off-by-one"])
def test_both_faults_fail_the_tiny_limits(model, reference, fault,
                                          monkeypatch):
    """The tiny cell's limit on the logits' error is 1e-3.  A sound
    program reads under it; a window layer's prefill without the band
    reads over it from the first token (a prompt of 52 against a window
    of 32); a ring written one entry off after its first wrap (a prompt
    of 40 fills entries 0-2; the step at position 48 begins block 3,
    which belongs in entry 0) reads over it from the step at position
    49, the first to look for a key of the misplaced block."""
    prompt = 40 if fault == "ring-off-by-one" else 52
    jax.clear_caches()
    try:
        err = _served_errors(model, reference, fault, monkeypatch, prompt)
    finally:
        monkeypatch.undo()
        jax.clear_caches()
    if fault == "sound":
        assert err.max() < 1e-3
    elif fault == "no-band":
        assert err[0] > 1e-3 and err.max() > 1e-2
    else:
        # err[0] is the prefill's token, err[k] the step at 39 + k
        assert err[:10].max() < 1e-3 and err[10:].max() > 1e-2


# ----------------------------------------------------------------------
# (i) the configuration, its count and its bytes


def _published():
    with open(CONFIG) as f:
        return json.load(f)


def _family():
    from benchmark.spec import load_module

    return load_module(os.path.join(ROOT, "benchmark", "models",
                                    "window_moe.py"), "family_window_moe")


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


# ----------------------------------------------------------------------
# (k) the new cell rehearsed through the benchmark's own command, at the
# tiny size on the CPU


@pytest.fixture(scope="module")
def tiny_benchmark(tmp_path_factory):
    """The real BENCHMARK.json cut to the new cell, its configuration
    the tiny one above (the real reference beside it), its traffic a
    few short requests, some of them over the tiny window."""
    from benchmark.spec import Spec

    root = tmp_path_factory.mktemp("tiny_benchmark")
    for sub in ("configs", "traffic", "limits"):
        os.makedirs(os.path.join(str(root), sub))
    tiny = copy.deepcopy(TINY)
    # the driver is handed a part of each decode row, as in the cell
    tiny["deployment"]["serve"]["checked_logit_parts"] = 5
    tiny["deployment"]["serve"]["num_blocks"] = [40, 16]
    with open(os.path.join(str(root), "configs", "tiny-st.json"), "w") as f:
        json.dump(tiny, f)
    shutil.copy(REFERENCE, os.path.join(str(root), "configs",
                                        "tiny-st.reference.py"))
    shutil.copy(os.path.join(ROOT, "benchmark", "tests", "tiny",
                             "peaks.json"), str(root))
    with open(os.path.join(ROOT, "benchmark", "traffic",
                           "serve-mixed-closed48-14k.json")) as f:
        traffic = json.load(f)
    assert traffic["clients"] == 48 and traffic["decode_buckets"] == [48]
    assert traffic["max_total_tokens"] == 14336 < 16384
    assert traffic["prefill_buckets"] == [512, 1024, 2048, 4096, 6144, 8192,
                                          12288]
    traffic.update(
        clients=4, requests=8, stagger_s=0.01, max_total_tokens=128,
        prompt_tokens=dict(traffic["prompt_tokens"], median=30, min=6,
                           max=90),
        new_tokens=dict(traffic["new_tokens"], median=10, min=4, max=30),
        prefill_buckets=[16, 64, 96], decode_buckets=[4],
        traced_seconds=0.3, checked_requests=3, request_timeout_s=60)
    with open(os.path.join(str(root), "traffic", "serve-tiny-14k.json"),
              "w") as f:
        json.dump(traffic, f)
    with open(os.path.join(str(root), "limits", "tiny-st-serve.json"),
              "w") as f:
        json.dump({"served_token_logit_gap": 1e-3,
                   "served_logit_abs_err": 1e-3}, f)
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        doc = json.load(f)
    doc["paths"], doc["run_seconds"] = ["."], 1
    doc["configs"] = [dict(c, name="tiny-st", source="test only",
                           file="configs/tiny-st.json")
                      for c in doc["configs"]
                      if c["name"] == "smallthinker-21b-ep4"]
    doc["workloads"] = [dict(w, name="tiny-st-serve", config="tiny-st",
                             traffic="serve-tiny-14k")
                        for w in doc["workloads"]
                        if w["name"] == "smallthinker-serve-mixed48"]
    assert len(doc["configs"]) == 1 and len(doc["workloads"]) == 1
    assert doc["workloads"][0]["chips"] == 1
    for m in doc["end_to_end"] + doc["per_layer"]:
        if "workloads" in m:
            m["workloads"] = ["tiny-st-serve"] \
                if "smallthinker-serve-mixed48" in m["workloads"] else []
    return Spec(str(root), doc=doc)


@pytest.mark.parametrize("trace", [0, 1])
def test_new_cell_rehearsed_on_the_cpu(tiny_benchmark, trace, capsys):
    from benchmark import run

    result = run.run_cell(tiny_benchmark, "tiny-st-serve",
                          3000000041 + trace, 1.5, trace,
                          require_chip=False)
    out = capsys.readouterr().out
    assert result["correct"] is True, out
    assert result["failed"] == 0 and result["attempted"] > 0, out
    assert "served_logit_abs_err" in out and " ok" in out
    metrics = result["metrics"]
    if trace:
        assert metrics["compiles_in_window"]["value"] == 0
        assert metrics["staged_gb_per_step"]["value"] == 0
        assert metrics["kv_occupancy_peak"]["value"] > 0
        assert 0 < metrics["window_kv_occupancy_peak.smallthinker"][
            "value"] <= 100
        assert 6 < metrics["decode_context_tokens_mean"]["value"] < 128
        assert 0 < metrics["window_keys_walked_share.smallthinker"][
            "value"] <= 100
        assert 0 <= metrics["decode_ahead_share"]["value"] < 100
        # all 8 tiny experts held: 3 pairs a row over a "16" that the
        # metric's file names for the real cell
        assert 0 < metrics["moe_tokens_per_held_expert.smallthinker"][
            "value"]
        assert 0 < metrics["moe_held_experts_hit_share.smallthinker"]["value"]
        # no device trace on a CPU: nothing read, nothing raised
        for name in ("gqa_paged_decode_roofline.smallthinker",
                     "window_paged_decode_roofline.smallthinker",
                     "window_prefill_roofline.smallthinker",
                     "window_decode_attn_share.smallthinker",
                     "moe_expert_roofline.smallthinker",
                     "device_idle_share.serve"):
            assert name not in metrics
    else:
        assert metrics["serve_tokens_per_s"]["value"] > 0
        # the cell reports no first-token time: a prompt mix this wide
        # puts the median near a bucket's edge
        assert "ttft_p50_ms" not in metrics
        assert metrics["setup_s"]["value"] > 0
    json.dumps(result)


def test_the_family_hands_the_driver_a_part_of_each_row(model):
    """Where the configuration gives ``checked_logit_parts`` the backend
    the family builds hands its caller, of every decode row, the part of
    the vocabulary its position names; ``num_blocks`` is the pair."""
    family = _family()
    tiny = copy.deepcopy(TINY)
    tiny["deployment"]["serve"]["checked_logit_parts"] = 5
    be = family.build_backend(tiny, tiny["deployment"]["serve"], model[1],
                              "wm_kept", lambda base: base)
    assert [g["blocks"] for g in be.cache.stats()["groups"]] == [32, 16]
    be.cache.allocate("s", 8)
    _prefill(be, "s", _tokens(4), 8)
    table = be.cache.block_table("s", be.max_blocks_per_seq)[None]
    out = be.decode([3], [4], table, [5])[0]
    assert out.values.shape == (1, 10) and out[0].part == slice(40, 50)


def test_reference_one_precision_down_is_not_the_reference(reference):
    """The float8 control mode moves the logits by far more than the
    float32 sides differ; bfloat16, the stated precision, lies between."""
    cfg = program_config(TINY)
    params = wm.init_params(cfg, 4, jnp.float32, SCALE)
    toks = _tokens(40, 25)[None]
    exact = np.asarray(reference.logits(TINY, params, toks))
    stated = np.asarray(reference.logits(TINY, params, toks, "bfloat16"))
    lower = np.asarray(reference.logits(TINY, params, toks, "float8"))
    assert TOL < np.abs(stated - exact).max() < np.abs(lower - exact).max()
    assert np.abs(lower - exact).max() > 0.1
    with pytest.raises(ValueError, match="unknown mode"):
        reference.logits(TINY, params, toks, "float16")


def test_unbuilt_variants_are_refused():
    with pytest.raises(ValueError, match="rope scaling"):
        wm.lm_config(dict(TINY, rope_scaling={"type": "yarn"}), 128)
    with pytest.raises(ValueError, match="softmax"):
        wm.lm_config(dict(TINY, moe_primary_router_apply_softmax=False), 128)
    with pytest.raises(ValueError, match="rope_layout"):
        wm.lm_config(dict(TINY, rope_layout=[0, 1, 1]), 128)
    with pytest.raises(ValueError, match="sliding_window_layout"):
        wm.lm_config(dict(TINY, sliding_window_layout=[0, 2, 1, 1, 0]), 128)
    # a model of global layers alone has one group, a plain cache
    plain = wm.lm_config(dict(TINY, sliding_window_layout=[0] * 8), 128)
    assert wm.cache_groups(plain) is None
    assert wm.table_widths(plain, 16) == (8, 0)
    banded = wm.lm_config(dict(TINY, sliding_window_layout=[1] * 8), 128)
    assert wm.cache_groups(banded) == (((0, 1, 2, 3, 4), 32),)
    assert wm.table_widths(banded, 16) == (0, 3)


def test_a_model_of_window_layers_alone_serves_through_one_ring(reference):
    """One group with a window: the pools are plain arrays, the table a
    ring, and the logits the reference's past the wrap."""
    tiny = dict(copy.deepcopy(TINY), num_hidden_layers=2,
                sliding_window_layout=[1] * 8)
    cfg = program_config(tiny)
    params = wm.init_params(cfg, 6, jnp.float32, SCALE)
    be = serving.LMBackend(
        params, definition=wm.lm_definition(cfg, jnp.float32),
        block_size=16, num_blocks=8, model="wm_ring_only")
    assert be.max_blocks_per_seq == 3
    assert be.cache.k_pages.shape == (2, 8, 16, 16)
    toks = list(_tokens(20, 27))
    be.cache.allocate("s", 100)
    assert be.cache.stats()["used"] == 3
    got = [_prefill(be, "s", toks, 32)]
    for t in range(20, 90):
        toks.append(int(np.argmax(got[-1])))
        got.append(_step(be, "s", toks[-1], t))
    want = _reference_logits(reference, params, toks, tiny)
    np.testing.assert_allclose(np.stack(got), want[19:], atol=TOL, rtol=0)


def test_a_closed_front_end_lets_go_of_the_backend_and_its_pools(model):
    """What the benchmark's driver needs of this cell: it keeps its
    front end's handle while the reference runs, and the reference's
    16,384-wide forward needs the room of the 7.4 GB of pools.  A closed
    front end holds no scheduler, so once the driver drops its own names
    the collector frees backend and pools (the benchmark's family
    collects before it hands the reference its weights)."""
    import gc
    import weakref

    be = _backend(model, "wm_close")
    sched = serving.GenerationScheduler(name="wm_close_sched")
    sched.register("wm_close", be, decode_buckets=[2],
                   prefill_buckets=[16])
    fe = serving.start_frontend(sched, timeout=10)
    assert fe.target is sched and fe.port > 0
    assert len(sched.generate("wm_close", [1, 2, 3], max_new_tokens=4)) == 4
    pools = weakref.ref(be.cache._groups[1].k_pages)
    cache = weakref.ref(be.cache)
    fe.close()
    sched.close()
    fe.close()                      # a second close is a no-op
    assert fe.target is None and fe.url.endswith(str(fe.port))
    del be, sched
    gc.collect()
    assert cache() is None and pools() is None


def test_serve_tool_loads_the_family_by_configuration(tmp_path):
    """``tools/serve.py --lm name=<configuration file>`` builds this
    family from the file's ``family`` key, like its siblings."""
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "serve_tool", os.path.join(ROOT, "tools", "serve.py"))
    tool = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(tool)
    path = tmp_path / "tiny-st.json"
    path.write_text(json.dumps(TINY))
    be = tool.lm_backend("tiny_tool_wm", "%s:7" % path)
    assert isinstance(be, serving.LMBackend)
    assert be.definition.cache_groups == (((0, 1), None), ((2, 3, 4), 32))
    assert be.max_blocks_per_seq == 11 and be.cfg["held"] == (0, 8)
    logits, k, v, counts = be.prefill(np.zeros(16, np.int32), 3)
    assert logits.shape == (50,) and k.shape == v.shape == (5, 16, 16)
