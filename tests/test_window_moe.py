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

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from mxnet_tpu import serving
from mxnet_tpu.models import window_moe as wm
from mxnet_tpu.ops.kv_cache import (CacheExhaustedError, CacheRow,
                                    PagedKVCache)

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


def _published():
    with open(CONFIG) as f:
        return json.load(f)


def _family():
    from benchmark.spec import load_module

    return load_module(os.path.join(ROOT, "benchmark", "models",
                                    "window_moe.py"), "family_window_moe")


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
    got = np.asarray(jax.jit(lambda p, t: wm.full_logits(p, t, cfg))(
        params, toks[None]))[0]
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
    program = jax.jit(lambda p, t: wm.full_logits(p, t, cfg))
    for logits in (lambda t: _reference_logits(reference, params, t, tiny),
                   lambda t: np.asarray(program(params, t[None]))[0]):
        np.testing.assert_allclose(logits(toks)[-1], logits(swapped)[-1],
                                   atol=1e-5, rtol=0)
    tiny, cfg, params = one_layer(1)
    changed = toks.copy()
    changed[39 - 32] = (toks[39 - 32] + 1) % 50       # just left the window
    inside = toks.copy()
    inside[39 - 31] = (toks[39 - 31] + 1) % 50        # the oldest key seen
    program = jax.jit(lambda p, t: wm.full_logits(p, t, cfg))
    for logits in (lambda t: _reference_logits(reference, params, t, tiny),
                   lambda t: np.asarray(program(params, t[None]))[0]):
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
# (g) the expert layer: ReLU as an argument, the router ahead


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
