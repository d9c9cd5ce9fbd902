"""The hybrid decoder of Gated DeltaNet layers and gated grouped-query
attention over sparse experts (``models/gated_delta_moe.py``) and what
it forced: the gated delta rule in its chunked and one-step forms, a
state pool of per-sequence slots beside the paged key and value pools,
a decode step whose recurrent update survives a repeat and a dropped
queued step, the softmax top-k router in front of the dropless expert
layer, and ``LMBackend`` handed a definition whose layers are of two
kinds.

Everything is held against the benchmark's plain reference
(``benchmark/configs/qwen3-next-ep4.reference.py``, which imports
nothing of the program) at a tiny size with the published *structure*:
one period of three DeltaNet layers and one full-attention layer, 2 key
heads serving 4 value heads, 4 query heads over 2 key-value heads with a
quarter of the head rotated, 16 experts of which 4 a token.  float32 on
the CPU, so the two sides differ by the order of float32 additions
only.
"""

import copy
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from mxnet_tpu import chaos, serving
from mxnet_tpu import observability as obs
from mxnet_tpu.base import MXNetError
from mxnet_tpu.models import gated_delta_moe as gm
from mxnet_tpu.observability import memory as obs_memory
from mxnet_tpu.ops import gated_delta as gd
from mxnet_tpu.ops.kv_cache import (CacheExhaustedError, CacheRow,
                                    PagedKVCache, StateRows)
from mxnet_tpu.parallel import moe

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REFERENCE = os.path.join(ROOT, "benchmark", "configs",
                         "qwen3-next-ep4.reference.py")
# the benchmark's configuration file at the tiny size: the published
# keys, the experts held (all 16 here), the deployment
TINY = {
    "family": "gated_delta_moe", "hidden_size": 32, "num_hidden_layers": 4,
    "full_attention_interval": 4, "num_attention_heads": 4,
    "num_key_value_heads": 2, "head_dim": 16, "partial_rotary_factor": 0.25,
    "rope_theta": 10000000, "rope_scaling": None,
    "linear_conv_kernel_dim": 4, "linear_key_head_dim": 8,
    "linear_num_key_heads": 2, "linear_num_value_heads": 4,
    "linear_value_head_dim": 8, "num_experts": 16, "num_experts_per_tok": 4,
    "moe_intermediate_size": 16, "shared_expert_intermediate_size": 16,
    "norm_topk_prob": True, "rms_norm_eps": 1e-6, "vocab_size": 50,
    "n_positions": 64,
    "deployment": {"experts": {"published": 16, "held": 16, "first": 0},
                   "serve": {"dtype": "float32", "block_size": 4,
                             "num_blocks": 256, "state_slots": 8}}}
# 0.3-wide weights: large enough that the experts, the rotary slice, the
# gates and the recurrence all move the logits
SCALE = 0.3
# what the two float32 sides may differ by, on logits of size ~5
TOL = 2e-4


def held_config(first=0, count=16):
    cfg = copy.deepcopy(TINY)
    cfg["num_experts"] = count
    cfg["deployment"]["experts"].update(held=count, first=first)
    return cfg


def program_config(cfg):
    share = cfg["deployment"]["experts"]
    return gm.lm_config(dict(cfg, num_experts=share["published"]),
                        seq_len=cfg["n_positions"],
                        held=(share["first"], share["held"]))


@pytest.fixture(scope="module")
def reference():
    from benchmark.spec import load_module

    return load_module(REFERENCE, "reference_qwen3next")


@pytest.fixture(scope="module")
def model():
    cfg = program_config(TINY)
    return cfg, gm.init_params(cfg, 0, jnp.float32, SCALE)


def _tokens(n, seed=0):
    return np.random.RandomState(seed).randint(
        0, TINY["vocab_size"], n).astype(np.int32)


def _backend(model, name, **kw):
    cfg, params = model
    kw.setdefault("num_blocks", 64)
    return serving.LMBackend(
        params, definition=gm.lm_definition(cfg, jnp.float32), block_size=4,
        model=name, state_slots=kw.pop("state_slots", 4), **kw)


def _prefill(be, seq, tokens, bucket):
    padded = np.zeros(bucket, np.int32)
    padded[:len(tokens)] = tokens
    logits, k, v, _, state = be.prefill(padded, len(tokens))
    be.cache.write_prefill(seq, k, v, len(tokens), state)
    return logits


def _step(be, seq, token, position, run_ahead=False):
    table = be.cache.block_table(seq, be.max_blocks_per_seq)[None]
    be.run_ahead = run_ahead
    try:
        return be.decode([token], [position], table, [position + 1])[0][0]
    finally:
        be.run_ahead = False


def _counter(name, **labels):
    want = ",".join('%s="%s"' % kv for kv in sorted(labels.items()))
    for line in obs.REGISTRY.render().splitlines():
        if line.startswith("%s{" % name) and all(
                part in line for part in want.split(",")):
            return float(line.rsplit(" ", 1)[1])
    return 0.0


# ----------------------------------------------------------------------
# (a) the full forward, (b) prefill then decode through both caches


def test_full_forward_is_the_reference_on_a_share(reference):
    """With a share of the experts held (ids 4-7 of 16): the reference
    leaves out what the absent twelve would add, as the program does."""
    tiny = held_config(first=4, count=4)
    cfg = program_config(tiny)
    assert cfg["held"] == (4, 4) and cfg["layer_types"] == (
        "linear", "linear", "linear", "full")
    params = gm.init_params(cfg, 1, jnp.float32, SCALE)
    assert params["l0_experts_gate_weight"].shape == (4, 32, 16)
    toks = _tokens(24, 3)
    want = np.asarray(reference.logits(tiny, params, toks[None]))[0]
    got = np.asarray(jax.jit(lambda p, t: gm.full_logits(p, t, cfg))(
        params, toks[None]))[0]
    assert np.abs(want).max() > 1.0
    np.testing.assert_allclose(got, want, atol=TOL, rtol=0)


@pytest.mark.parametrize("run_ahead", [False, True], ids=["alone", "ahead"])
@pytest.mark.parametrize("bucket", [5, 8, 16, 32])
def test_prefill_then_decode_through_both_caches_is_the_reference(
        model, reference, bucket, run_ahead):
    """A 5-token prompt at every bucket padding, then 15 greedy decode
    steps through ``LMBackend``: the full-attention layer through the
    paged key and value pools, the DeltaNet layers through the state
    pool.  Every step's logits against the reference's one forward over
    all 20 tokens; with run-ahead every call but the first is answered
    by the step queued behind the one before it."""
    be = _backend(model, "gdm_b%d%d" % (bucket, run_ahead))
    assert be.cache.k_pages.shape == (1, 64, 4, 32)     # one full layer
    assert [p.shape for p in be.cache.state_pools] == [
        (3 * 2 * 4 + 1, 4, 8, 8), (3 * 2 * 4 + 1, 3, 64)]
    prompt = _tokens(5, 7)
    be.cache.allocate("s", 20)
    got = [_prefill(be, "s", prompt, bucket)]
    toks = list(prompt)
    for t in range(5, 20):
        toks.append(int(np.argmax(got[-1])))
        got.append(_step(be, "s", toks[-1], t, run_ahead and t < 19))
    want = np.asarray(reference.logits(
        TINY, model[1], np.asarray(toks, np.int32)[None]))[0]
    np.testing.assert_allclose(np.stack(got), want[4:], atol=TOL, rtol=0)
    used = _counter("generation_decode_ahead_used_total", model=be.model)
    assert used == (14 if run_ahead else 0)
    assert _counter("generation_state_bytes_total", model=be.model) > 0


# ----------------------------------------------------------------------
# (c) the rule's forms, (d) a bucket's pad


def _rule_inputs(t, heads=3, dk=8, dv=16, seed=0):
    rng = np.random.RandomState(seed)

    def unit(x):
        return x / np.linalg.norm(x, axis=-1, keepdims=True)

    q = unit(rng.randn(t, heads, dk)).astype(np.float32)
    k = unit(rng.randn(t, heads, dk)).astype(np.float32)
    v = rng.randn(t, heads, dv).astype(np.float32)
    g = (-0.3 * np.abs(rng.randn(t, heads))).astype(np.float32)
    beta = (1 / (1 + np.exp(-rng.randn(t, heads)))).astype(np.float32)
    return q, k, v, g, beta, rng.randn(heads, dk, dv).astype(np.float32)


@pytest.mark.parametrize("carried", [False, True], ids=["empty", "carried"])
@pytest.mark.parametrize("chunk", [64, 50, 32, 7, 256])
def test_chunked_scan_equals_one_step_form_equals_plain_scan(
        reference, chunk, carried):
    """150 tokens: chunks that divide the length (50), that do not (64,
    32, 7) and one longer than it (256), from an empty state and from a
    carried-in one, against the reference's token-by-token scan and the
    decode form run 150 times."""
    q, k, v, g, beta, s0 = _rule_inputs(150)
    s0 = jnp.asarray(s0) if carried else None
    want_o, want_s = reference.delta_rule(*map(jnp.asarray,
                                               (q, k, v, g, beta)), state=s0)
    got_o, got_s = gd.gated_delta_chunked(q, k, v, g, beta, s0, chunk=chunk)
    np.testing.assert_allclose(got_o, want_o, atol=2e-5, rtol=0)
    np.testing.assert_allclose(got_s, want_s, atol=2e-5, rtol=0)
    state = (jnp.zeros_like(want_s) if s0 is None else s0)[None]
    outs = []
    for t in range(150):
        o, state = gd.gated_delta_step(q[t][None], k[t][None], v[t][None],
                                       g[t][None], beta[t][None], state)
        outs.append(o[0])
    np.testing.assert_allclose(np.stack(outs), want_o, atol=2e-5, rtol=0)
    np.testing.assert_allclose(state[0], want_s, atol=2e-5, rtol=0)


def test_a_token_with_no_write_and_no_decay_leaves_the_state():
    q, k, v, g, beta, s0 = _rule_inputs(40)
    g[25:], beta[25:] = 0.0, 0.0
    _, cut = gd.gated_delta_chunked(q[:25], k[:25], v[:25], g[:25],
                                    beta[:25], jnp.asarray(s0), chunk=16)
    _, padded = gd.gated_delta_chunked(q, k, v, g, beta, jnp.asarray(s0),
                                       chunk=16)
    np.testing.assert_allclose(padded, cut, atol=1e-6, rtol=0)


@pytest.fixture(scope="module")
def prefill_program(model):
    """One jitted prefill for the module: a bucket compiles once."""
    cfg, _ = model
    return jax.jit(lambda p, t, n: gm.prefill(p, t, n, cfg))


@pytest.mark.parametrize("length", [1, 2, 5, 11])
def test_a_prefills_pad_rows_leave_state_and_tail_as_at_length(
        model, prefill_program, length):
    """The state and the convolution's tail a prefill returns are those
    after token ``length - 1`` whatever the bucket: the pad positions
    pass through with ``beta = 0`` and ``g = 0`` and the tail is taken
    at ``length`` (zeros before the start of a prompt shorter than the
    kernel)."""
    cfg, params = model
    toks = _tokens(16, 5)
    exact = prefill_program(params, jnp.asarray(toks[:length]), length)
    for bucket in (12, 16):
        padded = np.full(bucket, 33, np.int32)          # pad is not zero
        padded[:length] = toks[:length]
        got = prefill_program(params, jnp.asarray(padded), length)
        np.testing.assert_allclose(got[0], exact[0], atol=1e-5, rtol=0)
        for a, b in zip(got[4], exact[4]):
            np.testing.assert_allclose(a, b, atol=1e-5, rtol=0)
    tail = np.asarray(exact[4][1])                      # [3 layers, 3, 64]
    assert not tail[:, :max(0, 3 - length)].any()
    assert tail[:, max(0, 3 - length):].any()


# ----------------------------------------------------------------------
# (e) a repeated step, a dropped queued step, a retry behind a queued step


def _reference_logits(reference, params, toks):
    return np.asarray(reference.logits(
        TINY, params, np.asarray(toks, np.int32)[None]))[0]


def test_a_repeated_step_leaves_the_state_as_if_run_once(model, reference):
    """Every decode step dispatched twice (a retry): the second call
    reads the version of the state the first one read, so both return
    the same logits and the sequence goes on as the reference's."""
    be = _backend(model, "gdm_repeat")
    toks = _tokens(16, 9)
    want = _reference_logits(reference, model[1], toks)
    be.cache.allocate("s", 16)
    _prefill(be, "s", toks[:4], 8)
    for t in range(4, 16):
        first = _step(be, "s", toks[t], t)
        again = _step(be, "s", toks[t], t)
        np.testing.assert_array_equal(first, again)
        np.testing.assert_allclose(again, want[t], atol=TOL, rtol=0)


def test_a_dropped_queued_step_leaves_the_state_as_if_run_once(model,
                                                               reference):
    """Every step queues the step after it, fed by its own greedy id;
    the next call asks for another token, so the queued step (which has
    already advanced the state it wrote) is dropped and the step is
    dispatched afresh: its logits are the reference's for the tokens
    that were really consumed."""
    be = _backend(model, "gdm_drop")
    toks = _tokens(16, 11)
    want = _reference_logits(reference, model[1], toks)
    be.cache.allocate("s", 16)
    _prefill(be, "s", toks[:4], 8)
    for t in range(4, 16):
        got = _step(be, "s", toks[t], t, run_ahead=t < 15)
        np.testing.assert_allclose(got, want[t], atol=TOL, rtol=0)
    dropped = _counter("generation_decode_ahead_dropped_total",
                       model="gdm_drop")
    assert dropped >= 9            # a greedy id may equal the token fed


def test_a_step_that_fails_behind_a_queued_step_is_a_hazard(model):
    """The step after it was queued (and overwrote the version of the
    state this step read) when the step's fetch failed: the error says
    so, and without a queued step the plain error comes through."""
    be = _backend(model, "gdm_hazard")
    be.cache.allocate("s", 16)
    _prefill(be, "s", _tokens(4), 8)
    with chaos.inject("serving.decode", "raise", match=":fetch", limit=1):
        with pytest.raises(chaos.ChaosError):
            _step(be, "s", 3, 4)
    with chaos.inject("serving.decode", "raise", match=":fetch", limit=1):
        with pytest.raises(serving.RecurrentStateHazard):
            _step(be, "s", 3, 4, run_ahead=True)
    assert be._ahead is None


@pytest.mark.parametrize("where", ["before", "behind"])
def test_faults_under_the_scheduler_never_serve_a_twice_advanced_state(
        model, where):
    """Two requests fill the decode batch, so every step runs ahead.
    Faults at the ``serving.decode`` site before the device call are
    retried in place (the repeated step) and drop queued steps; faults
    behind the dispatch, with the next step queued, cannot be: the live
    sequences are re-prefilled over what they have.  Either way every
    served token is the greedy choice of a full forward over the tokens
    before it."""
    cfg, params = model
    name = "gdm_sched_" + where
    be = _backend(model, name, state_slots=2)
    sched = serving.GenerationScheduler(name=name)
    try:
        sched.register(name, be, decode_buckets=[2], prefill_buckets=[8, 32])
        sched.warmup(name)
        prompts = [_tokens(6, 21), _tokens(3, 22)]
        match = ":2" if where == "before" else ":fetch"
        with chaos.inject("serving.decode", "raise", prob=0.25, seed=5,
                          match=match) as inj:
            with sched._lanes[name].entry.dispatch_lock:
                reqs = [sched.submit(name, p, max_new_tokens=20)
                        for p in prompts]
            outs = [r.result(timeout=120) for r in reqs]
        assert inj.fires > 0
        forward = jax.jit(lambda t: gm.full_logits(params, t, cfg)[0])
        for prompt, out in zip(prompts, outs):
            assert len(out) == 20
            seq = np.zeros(32, np.int32)     # causal: the pad is unseen
            seq[:len(prompt)], n = prompt, len(prompt)
            for tok in out:
                want = np.asarray(forward(seq[None]))[n - 1]
                assert want[tok] >= want.max() - 1e-4
                seq[n], n = tok, n + 1
        resumed = _counter("generation_state_hazard_total", model=name,
                           outcome="resumed")
        assert (resumed > 0) == (where == "behind")
        assert _counter("generation_state_hazard_total", model=name,
                        outcome="failed") == 0
        assert _counter("generation_decode_ahead_used_total",
                        model=name) > 0
        assert be.cache.stats()["state_slots_used"] == 0
    finally:
        sched.close()


# ----------------------------------------------------------------------
# (f) the shares add up, (g) the router


def _layer_weights(params, prefix="l0_"):
    return {k[len(prefix):]: v for k, v in params.items()
            if k.startswith(prefix)}


def _expert_update(params, cfg, x, held):
    """The program's expert layer of layer 0 over ``x`` with ``held``
    experts' weights, and its shared expert alone."""
    first, count = held
    cut = dict(params)
    for name in ("gate", "up", "down"):
        key = "l0_experts_%s_weight" % name
        cut[key] = params[key][first:first + count]
    out, counts = gm._feed_forward(cut, 0, x, dict(cfg, held=held))
    return out, counts


@pytest.mark.parametrize("every_row", [False, True],
                         ids=["grouped", "every_row"])
@pytest.mark.parametrize("shares", [1, 2, 4, 16])
def test_the_shares_add_up_to_the_uncut_layer(model, reference, shares,
                                              every_row, monkeypatch):
    """The routed parts of 1, 2, 4 (the deployment's 512 / 128) and 16
    shares of the 16 experts, with the shared expert counted once, are
    the uncut layer, which is the reference's."""
    cfg, params = model
    monkeypatch.setattr(moe, "few_rows_hit_most", lambda *s: every_row)
    x = jnp.asarray(np.random.RandomState(3).randn(24, 32), jnp.float32)
    w = _layer_weights(params)
    h = gm._norm(x, w["ffn_norm_gamma"], cfg)
    shared = moe.gated_shared_expert(
        h, w["shared_gate_weight"], w["shared_up_weight"],
        w["shared_down_weight"], w["shared_expert_gate_weight"])
    per = 16 // shares
    total, local = 0.0, 0
    for s in range(shares):
        out, counts = _expert_update(params, cfg, x, (s * per, per))
        total = total + (out - shared)
        local += int(counts[1])
        assert int(counts[0]) == 24 * 4
    assert local == 24 * 4              # every pair fell on one share
    want = reference._expert_layer(TINY, w, x, reference._Math("float32"))
    np.testing.assert_allclose(total + shared, want, atol=2e-5, rtol=0)


def test_softmax_router_is_the_reference_exactly(reference):
    logits = jnp.asarray(np.random.RandomState(1).randn(200, 16) * 2,
                         jnp.float32)
    chosen, gates = moe.route_softmax_topk(logits, top_k=4)
    want_c, want_g = reference.route(TINY, logits)
    np.testing.assert_array_equal(np.sort(chosen, 1), np.sort(want_c, 1))
    np.testing.assert_allclose(np.sort(gates, 1), np.sort(want_g, 1),
                               rtol=1e-6)
    np.testing.assert_allclose(gates.sum(1), 1.0, rtol=1e-6)
    raw = moe.route_softmax_topk(logits, top_k=4, normalize=False)[1]
    assert (raw.sum(1) < 1.0).all()
    np.testing.assert_allclose(raw / raw.sum(1, keepdims=True), gates,
                               rtol=1e-6)


@pytest.mark.parametrize("tokens", [1, 24, 200])
def test_no_token_is_dropped_at_any_skew(model, reference, tokens):
    """A router pushed so that every token chooses the same four
    experts: the layer is still the reference's (a capacity-bound layer
    would drop all but a few)."""
    cfg, params = model
    skewed = dict(params)
    skewed["l0_router_weight"] = params["l0_router_weight"].at[:4].add(
        5.0 * jnp.sign(params["l0_router_weight"][:4]))
    x = jnp.abs(jnp.asarray(np.random.RandomState(tokens).randn(tokens, 32),
                            jnp.float32))
    out, counts = gm._feed_forward(skewed, 0, x, cfg)
    want = reference._expert_layer(TINY, _layer_weights(skewed), x,
                                   reference._Math("float32"))
    np.testing.assert_allclose(out, want, atol=5e-5, rtol=0)
    assert int(counts[1]) == tokens * 4


# ----------------------------------------------------------------------
# (i) two kinds of state in one cache


def test_the_cache_frees_blocks_and_slot_and_either_runs_out_typed():
    state = StateRows(2, (((2, 4, 4), np.dtype(np.float32)),
                          ((3, 8), np.dtype(np.float32))))
    assert state.bytes == 2 * (32 * 4 + 24 * 4)
    obs_memory._reset_ledger()
    cache = PagedKVCache(num_layers=1, row=CacheRow("kv", 8, np.float32, 2),
                         block_size=4, num_blocks=6, model="gdm_cache",
                         state=state, state_slots=2)
    # two versions of two slots over two layers, and the pad rows' row
    assert cache.state_bytes == 2 * 2 * state.bytes + state.bytes // 2
    assert [p.shape for p in cache.state_pools] == [(9, 2, 4, 4), (9, 3, 8)]
    ledger = dict(obs_memory.ledger_entries())
    assert ledger[("kv_cache", id(cache))][0] == cache.pool_bytes
    assert ledger[("recurrent_state", id(cache))][0] == cache.state_bytes
    assert sum(int(p.nbytes) for p in cache.state_pools) \
        == cache.state_bytes
    cache.allocate("a", 8)
    cache.allocate("b", 8)
    assert cache.stats()["state_slots_used"] == 2
    assert _counter("serving_state_slots_used", model="gdm_cache") == 2
    assert _counter("serving_state_bytes", model="gdm_cache") \
        == 2 * 2 * state.bytes
    # blocks are left (2 of 6) but no slot: the typed 429, and nothing
    # was taken
    with pytest.raises(CacheExhaustedError, match="state slot") as err:
        cache.allocate("c", 4)
    assert err.value.http_status == 429
    assert cache.stats()["used"] == 4 and "c" not in cache.sequences()
    cache.free("a")
    assert cache.stats()["state_slots_used"] == 1
    # a slot is free now but not the blocks: the same error, no slot kept
    with pytest.raises(CacheExhaustedError, match="more block"):
        cache.allocate("c", 20)
    assert cache.stats()["state_slots_used"] == 1
    cache.allocate("c", 8)              # growing keeps the slot it has
    cache.allocate("c", 12)
    assert cache.stats()["state_slots_used"] == 2
    slots = cache.state_slots(
        np.stack([cache.block_table(s, 3) for s in ("b", "c")] * 2)[:3],
        [5, 7, 0])
    assert sorted(slots[:2]) == [0, 1] and slots[2] == 2    # a pad row
    cache.free("b")
    cache.free("c")
    assert cache.stats()["used"] == 0
    assert cache.stats()["state_slots_used"] == 0
    assert _counter("serving_state_slots_used", model="gdm_cache") == 0


def test_a_model_with_state_names_its_slots(model):
    """No default and no environment variable stands in for
    ``state_slots``: a pool that did not match the decode buckets would
    turn admissions into 429s."""
    state = StateRows(1, (((2, 4), np.dtype(np.float32)),))
    with pytest.raises(MXNetError, match="needs state_slots"):
        PagedKVCache(num_layers=1, row=CacheRow("kv", 8, np.float32, 2),
                     block_size=4, num_blocks=4, state=state)
    with pytest.raises(MXNetError, match="needs state_slots"):
        serving.LMBackend(model[1],
                          definition=gm.lm_definition(model[0], jnp.float32),
                          block_size=4, num_blocks=8, model="gdm_noslots")
    assert PagedKVCache(num_layers=1, num_heads=1, head_dim=8, block_size=4,
                        num_blocks=4).num_slots == 0


def test_a_prefill_writes_the_version_its_first_step_reads():
    state = StateRows(2, (((2, 4), np.dtype(np.float32)),))
    cache = PagedKVCache(num_layers=1, row=CacheRow("kv", 8, np.float32, 2),
                         block_size=4, num_blocks=4, model="gdm_version",
                         state=state, state_slots=3)
    cache.allocate("s", 8)
    slot = cache._slots["s"]
    rows = jnp.arange(16, dtype=jnp.float32).reshape(2, 2, 4) + 1
    k = jnp.zeros((1, 8, 8))
    cache.write_prefill("s", k, k, 5, (rows,))
    pool = np.asarray(cache.state_pools[0])[:-1].reshape(2, 2, 3, 2, 4)
    np.testing.assert_array_equal(pool[:, 1, slot], np.asarray(rows))
    assert not pool[:, 0].any()
    assert _counter("serving_state_prefill_slots_total",
                    model="gdm_version") == 1
