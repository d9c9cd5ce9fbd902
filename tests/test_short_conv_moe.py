"""The hybrid decoder of gated short-convolution mixers and grouped-query
attention over sparse experts (``models/short_conv_moe.py``) and what it
forced: a per-layer list of mixer kinds with leading dense layers, a
state pool whose only row is the convolution's tail, a decode step whose
state survives a repeat and a dropped queued step, the sigmoid router
with a selection bias and its renormalisation's epsilon in front of the
dropless expert layer with every expert held, and ``LMBackend`` handed a
definition whose cached layers and state layers index into an irregular
``layer_types``.

Everything is held against the benchmark's plain reference
(``benchmark/configs/lfm2-8b-a1b-pp2.reference.py``, which imports
nothing of the program) at a tiny size with the published *structure*:
the first seven of eight listed layers (``c c A c c c A``: two leading
dense layers, an irregular pattern, five state layers and two cached
ones), 4 query heads over 2 key-value heads, three taps, 8 experts of
which 2 a token.  float32 on the CPU, so the two sides differ by the
order of float32 additions only.
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
from mxnet_tpu.models import short_conv_moe as sc
from mxnet_tpu.parallel import moe

# what drives a backend by hand and reads a counter is the same for
# every model with a state
from test_gated_delta_moe import _counter, _prefill, _step

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CONFIG = os.path.join(ROOT, "benchmark", "configs", "lfm2-8b-a1b-pp2.json")
REFERENCE = CONFIG[:-len(".json")] + ".reference.py"
# the benchmark's configuration file at the tiny size: the published
# keys, the experts held (all 8 here), the deployment
TINY = {
    "family": "short_conv_moe", "hidden_size": 32, "intermediate_size": 48,
    "moe_intermediate_size": 16, "num_hidden_layers": 7,
    "layer_types": ["conv", "conv", "full_attention", "conv", "conv", "conv",
                    "full_attention", "conv"],
    "num_attention_heads": 4, "num_key_value_heads": 2, "conv_L_cache": 3,
    "conv_bias": False, "num_dense_layers": 2, "num_experts": 8,
    "num_experts_per_tok": 2, "norm_topk_prob": True,
    "routed_scaling_factor": 1, "use_expert_bias": True, "norm_eps": 1e-5,
    "rope_theta": 1000000, "vocab_size": 50, "n_positions": 64,
    "deployment": {"experts": {"published": 8, "held": 8, "first": 0},
                   "serve": {"dtype": "float32", "block_size": 4,
                             "num_blocks": 256, "state_slots": 8}}}
# 0.3-wide weights and a 0.1-wide selection bias: large enough that the
# experts, the rotary, the gates and the convolution all move the logits
SCALE, BIAS = 0.3, 0.1
# what the two float32 sides may differ by, on logits of size ~5
TOL = 2e-4


def held_config(first=0, count=8):
    cfg = copy.deepcopy(TINY)
    cfg["num_experts"] = count
    cfg["deployment"]["experts"].update(held=count, first=first)
    return cfg


def program_config(cfg):
    share = cfg["deployment"]["experts"]
    return sc.lm_config(dict(cfg, num_experts=share["published"]),
                        seq_len=cfg["n_positions"],
                        held=(share["first"], share["held"]))


@pytest.fixture(scope="module")
def reference():
    from benchmark.spec import load_module

    return load_module(REFERENCE, "reference_lfm2")


@pytest.fixture(scope="module")
def model():
    cfg = program_config(TINY)
    return cfg, sc.init_params(cfg, 0, jnp.float32, SCALE, BIAS)


def _tokens(n, seed=0):
    return np.random.RandomState(seed).randint(
        0, TINY["vocab_size"], n).astype(np.int32)


def _backend(model, name, **kw):
    cfg, params = model
    kw.setdefault("num_blocks", 64)
    return serving.LMBackend(
        params, definition=sc.lm_definition(cfg, jnp.float32), block_size=4,
        model=name, state_slots=kw.pop("state_slots", 4), **kw)


def _reference_logits(reference, params, toks):
    return np.asarray(reference.logits(
        TINY, params, np.asarray(toks, np.int32)[None]))[0]


# ----------------------------------------------------------------------
# (a) the full forward, (b) prefill then decode through both caches


def test_full_forward_is_the_reference_on_a_share(reference):
    """With a share of the experts held (ids 2-5 of 8): the reference
    leaves out what the absent four would add, as the program does."""
    tiny = held_config(first=2, count=4)
    cfg = program_config(tiny)
    assert cfg["held"] == (2, 4) and cfg["head_dim"] == 8
    assert cfg["layer_types"] == ("conv", "conv", "full_attention", "conv",
                                  "conv", "conv", "full_attention")
    params = sc.init_params(cfg, 1, jnp.float32, SCALE, BIAS)
    assert params["l2_experts_gate_weight"].shape == (4, 32, 16)
    assert "l0_router_weight" not in params and "l1_ffn_up_weight" in params
    toks = _tokens(24, 3)
    want = np.asarray(reference.logits(tiny, params, toks[None]))[0]
    got = np.asarray(jax.jit(lambda p, t: sc.full_logits(p, t, cfg))(
        params, toks[None]))[0]
    assert np.abs(want).max() > 1.0
    np.testing.assert_allclose(got, want, atol=TOL, rtol=0)


@pytest.mark.parametrize("run_ahead", [False, True], ids=["alone", "ahead"])
@pytest.mark.parametrize("bucket", [5, 8, 16, 32])
def test_prefill_then_decode_through_both_caches_is_the_reference(
        model, reference, bucket, run_ahead):
    """A 5-token prompt at every bucket padding, then 15 greedy decode
    steps through ``LMBackend``: the attention layers through the paged
    key and value pools, the convolution layers through the state pool.
    Every step's logits against the reference's one forward over all 20
    tokens; with run-ahead every call but the first is answered by the
    step queued behind the one before it."""
    be = _backend(model, "scm_b%d%d" % (bucket, run_ahead))
    assert be.cache.k_pages.shape == (2, 64, 4, 16)     # two cached layers
    # five state layers, two versions of four slots, the pad rows' row:
    # two tail rows of 32 channels
    assert [p.shape for p in be.cache.state_pools] == [(5 * 2 * 4 + 1, 2, 32)]
    prompt = _tokens(5, 7)
    be.cache.allocate("s", 20)
    got = [_prefill(be, "s", prompt, bucket)]
    toks = list(prompt)
    for t in range(5, 20):
        toks.append(int(np.argmax(got[-1])))
        got.append(_step(be, "s", toks[-1], t, run_ahead and t < 19))
    want = _reference_logits(reference, model[1], toks)
    np.testing.assert_allclose(np.stack(got), want[4:], atol=TOL, rtol=0)
    used = _counter("generation_decode_ahead_used_total", model=be.model)
    assert used == (14 if run_ahead else 0)
    # every step moved one row's state once each way: 5 layers of 2 x 32
    # float32 values
    assert _counter("generation_state_bytes_total", model=be.model) \
        == 15 * 2 * 5 * 2 * 32 * 4
    assert _counter("serving_state_slots_used", model=be.model) == 1
    assert _counter("kv_cache_layers", model=be.model) == 2
    assert _counter("kv_cache_row_bytes", model=be.model) == 2 * 16 * 4


# ----------------------------------------------------------------------
# (c) the convolution's two forms, (d) a bucket's pad


@pytest.mark.parametrize("carried", [0, 1, 2, 9], ids=lambda n: "from%d" % n)
def test_convolution_over_a_prompt_equals_the_one_step_form(model, reference,
                                                            carried):
    """The mixer over 16 tokens at once against the one-step form a
    token at a time, carried from an empty state (``carried`` 0) or
    from the tail a prefill of the first ``carried`` tokens returns;
    and the convolution alone against the reference's three shifted
    products."""
    cfg, params = model
    x = jnp.asarray(np.random.RandomState(carried).randn(16, 32),
                    jnp.float32)
    whole, tail_end = sc._conv_prefill(params, "l0_", x, None, cfg)
    tail = jnp.zeros((1, 2, 32), jnp.float32)
    if carried:
        head, tail = sc._conv_prefill(params, "l0_", x[:carried], None, cfg)
        np.testing.assert_allclose(head, whole[:carried], atol=2e-5, rtol=0)
        tail = tail[None]
    for t in range(carried, 16):
        update, tail = sc._conv_decode(params, "l0_", x[t:t + 1], tail, cfg)
        np.testing.assert_allclose(update[0], whole[t], atol=2e-5, rtol=0)
    np.testing.assert_allclose(tail[0], tail_end, atol=2e-5, rtol=0)
    u, _ = sc._conv_inputs(params, "l0_", x, cfg)
    np.testing.assert_array_equal(tail_end, u[-2:])
    want = reference.short_conv(u, params["l0_conv_weight"])
    got = sum(jnp.pad(u, ((2, 0), (0, 0)))[j:j + 16]
              * params["l0_conv_weight"][:, j] for j in range(3))
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=0)
    # the last tap is on the current token, the first two positions see
    # zeros before the prompt
    np.testing.assert_allclose(want[0], u[0] * params["l0_conv_weight"][:, 2],
                               atol=2e-5, rtol=0)


@pytest.fixture(scope="module")
def prefill_program(model):
    """One jitted prefill for the module: a bucket compiles once."""
    cfg, _ = model
    return jax.jit(lambda p, t, n: sc.prefill(p, t, n, cfg))


@pytest.mark.parametrize("length", [1, 2, 5, 11])
def test_a_prefills_pad_rows_leave_the_state_as_at_length(
        model, prefill_program, length):
    """The tail a prefill returns is the gated inputs of tokens ``length
    - 2`` and ``length - 1`` whatever the bucket and whatever the pad
    holds (zeros before the start of a prompt shorter than the taps)."""
    cfg, params = model
    toks = _tokens(16, 5)
    exact = prefill_program(params, jnp.asarray(toks[:length]), length)
    for bucket in (12, 16):
        padded = np.full(bucket, 33, np.int32)          # pad is not zero
        padded[:length] = toks[:length]
        got = prefill_program(params, jnp.asarray(padded), length)
        np.testing.assert_allclose(got[0], exact[0], atol=1e-5, rtol=0)
        assert len(got[4]) == 1 and got[4][0].shape == (5, 2, 32)
        np.testing.assert_allclose(got[4][0], exact[4][0], atol=1e-5,
                                   rtol=0)
    tail = np.asarray(exact[4][0])                      # [5 layers, 2, 32]
    assert not tail[:, :max(0, 2 - length)].any()
    assert tail[:, max(0, 2 - length):].any()


# ----------------------------------------------------------------------
# (e) a repeated step, a dropped queued step


def test_a_repeated_step_leaves_the_state_as_if_run_once(model, reference):
    """Every decode step dispatched twice (a retry): the second call
    reads the version of the state the first one read, so both return
    the same logits and the sequence goes on as the reference's."""
    be = _backend(model, "scm_repeat")
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
    be = _backend(model, "scm_drop")
    toks = _tokens(16, 11)
    want = _reference_logits(reference, model[1], toks)
    be.cache.allocate("s", 16)
    _prefill(be, "s", toks[:4], 8)
    for t in range(4, 16):
        got = _step(be, "s", toks[t], t, run_ahead=t < 15)
        np.testing.assert_allclose(got, want[t], atol=TOL, rtol=0)
    assert _counter("generation_decode_ahead_dropped_total",
                    model="scm_drop") >= 9   # a greedy id may be the fed one


def test_a_step_that_fails_behind_a_queued_step_is_a_hazard(model):
    """A state with no kernel of its own is a recurrent state all the
    same: once the queued step has overwritten the version this step
    read, its failed fetch cannot be retried in place."""
    from mxnet_tpu import chaos

    be = _backend(model, "scm_hazard")
    be.cache.allocate("s", 16)
    _prefill(be, "s", _tokens(4), 8)
    with chaos.inject("serving.decode", "raise", match=":fetch", limit=1):
        with pytest.raises(serving.RecurrentStateHazard):
            _step(be, "s", 3, 4, run_ahead=True)
    assert be._ahead is None


def test_two_sequences_keep_their_own_states_in_one_batch(model, reference):
    """Two sequences of different lengths decoded in one batch with a
    pad row between steps' slots: each row reads and writes its own
    slot's version, the pad row the pool's last row."""
    be = _backend(model, "scm_two")
    seqs = {"a": _tokens(14, 1), "b": _tokens(11, 2)}
    starts = {"a": 6, "b": 3}
    want = {s: _reference_logits(reference, model[1], t)
            for s, t in seqs.items()}
    for s, toks in seqs.items():
        be.cache.allocate(s, len(toks))
        _prefill(be, s, toks[:starts[s]], 8)
    tables = np.stack([be.cache.block_table(s, be.max_blocks_per_seq)
                       for s in ("a", "b")]
                      + [np.zeros(be.max_blocks_per_seq, np.int32)])
    for step in range(8):
        at = [starts["a"] + step, starts["b"] + step]
        logits = be.decode(
            [seqs["a"][at[0]], seqs["b"][at[1]], 0], at + [0], tables,
            [at[0] + 1, at[1] + 1, 0])[0]
        for row, s in enumerate(("a", "b")):
            np.testing.assert_allclose(logits[row], want[s][at[row]],
                                       atol=TOL, rtol=0)


# ----------------------------------------------------------------------
# (f) the shares add up, (g) the router, (h) no token dropped


def _layer_weights(params, prefix="l2_"):
    return {k[len(prefix):]: v for k, v in params.items()
            if k.startswith(prefix)}


def _expert_update(params, cfg, x, held):
    """The program's expert layer of layer 2 (the first with experts)
    over ``x`` with ``held`` experts' weights."""
    first, count = held
    cut = dict(params)
    for name in ("gate", "up", "down"):
        key = "l2_experts_%s_weight" % name
        cut[key] = params[key][first:first + count]
    return sc._feed_forward(cut, 2, x, dict(cfg, held=held))


@pytest.mark.parametrize("every_row", [False, True],
                         ids=["grouped", "every_row"])
@pytest.mark.parametrize("holders", [1, 2, 4])
def test_the_shares_add_up_to_the_uncut_layer(model, reference, holders,
                                              every_row, monkeypatch):
    """The 32 experts of the deployment are all held by one chip; split
    over 1, 2 and 4 holders (8, 4 and 2 of the tiny 8 each) the parts
    sum to the uncut layer, which is the reference's: there is no
    shared expert to count once."""
    cfg, params = model
    monkeypatch.setattr(moe, "few_rows_hit_most", lambda *s: every_row)
    x = jnp.asarray(np.random.RandomState(3).randn(24, 32), jnp.float32)
    per = 8 // holders
    total, local = 0.0, 0
    for s in range(holders):
        out, counts = _expert_update(params, cfg, x, (s * per, per))
        total = total + out
        local += int(counts[1])
        assert int(counts[0]) == 24 * 2
    assert local == 24 * 2              # every pair fell on one holder
    want = reference._expert_layer(TINY, _layer_weights(params), x,
                                   reference._Math("float32"))
    np.testing.assert_allclose(total, want, atol=2e-5, rtol=0)


def test_sigmoid_router_is_the_reference_exactly(reference):
    """Choices, gates, ties and the epsilon: equal scores go to the
    lower id on both sides, and the gates' sum falls short of 1 by the
    published 1e-6 over it (not by the 1e-20 of the DeepSeek router,
    which is still that function's default)."""
    rng = np.random.RandomState(1)
    logits = jnp.asarray(rng.randn(200, 8) * 2, jnp.float32)
    logits = logits.at[:50, 3].set(logits[:50, 5])      # ties
    bias = jnp.asarray(rng.randn(8) * 0.1, jnp.float32).at[3].set(0.0) \
        .at[5].set(0.0)
    chosen, gates = moe.route_group_limited(
        logits, bias, top_k=2, scale=1, eps=sc.GATE_SUM_EPS)
    want_c, want_g = reference.route(TINY, logits, bias)
    np.testing.assert_array_equal(chosen, want_c)
    np.testing.assert_allclose(gates, want_g, rtol=1e-6)
    both = np.asarray((chosen == 3).any(1) & (chosen == 5).any(1))
    one = np.asarray((chosen == 3).any(1) ^ (chosen == 5).any(1))[:50]
    assert both[:50].any() and (np.asarray(chosen)[:50][one] != 5).all()
    s = np.take_along_axis(np.asarray(jax.nn.sigmoid(logits)),
                           np.asarray(chosen), 1)
    np.testing.assert_allclose(gates, s / (s.sum(1, keepdims=True) + 1e-6),
                               rtol=1e-6)
    # the choice is made with the bias, the gates are without it
    plain = moe.route_group_limited(logits, jnp.zeros(8), top_k=2)[0]
    assert (np.asarray(plain) != np.asarray(chosen)).any()
    # the default epsilon is the one the latent family publishes
    tiny_s = jnp.full((4, 8), -23.0)                    # scores ~1e-10
    default = moe.route_group_limited(tiny_s, jnp.zeros(8), top_k=2)[1]
    ours = moe.route_group_limited(tiny_s, jnp.zeros(8), top_k=2,
                                   eps=1e-6)[1]
    assert float(default.sum(1)[0]) > 0.99 and float(ours.sum(1)[0]) < 1e-3


@pytest.mark.parametrize("tokens", [1, 24, 200])
def test_no_token_is_dropped_at_any_skew(model, reference, tokens):
    """A router pushed so that every token chooses the same two experts
    (their scores saturate, so the selection bias is pushed too): the
    layer is still the reference's (a capacity-bound layer would drop
    all but a few)."""
    cfg, params = model
    skewed = dict(params)
    skewed["l2_router_weight"] = params["l2_router_weight"].at[:2].set(2.0)
    skewed["l2_expert_bias"] = params["l2_expert_bias"].at[:2].add(1.0)
    x = jnp.abs(jnp.asarray(np.random.RandomState(tokens).randn(tokens, 32),
                            jnp.float32))
    out, counts = sc._feed_forward(skewed, 2, x, cfg)
    want = reference._expert_layer(TINY, _layer_weights(skewed), x,
                                   reference._Math("float32"))
    np.testing.assert_allclose(out, want, atol=5e-5, rtol=0)
    assert int(counts[1]) == tokens * 2 and int(counts[2]) == 2


# ----------------------------------------------------------------------
# (j) the comparison sees the new mechanism


@pytest.mark.parametrize("fault", ["sound", "zeroed", "stale"])
def test_a_lost_state_fails_the_tiny_limits(model, reference, fault):
    """The control that the cell's ``correct`` sees the convolution
    state: zeroed at the hand-over from prefill to decode, or left one
    step old, it moves the served logits past the limit the tiny cell
    runs under (1e-3); left alone they are within it."""
    be = _backend(model, "scm_fault_" + fault)
    toks = _tokens(14, 13)
    want = _reference_logits(reference, model[1], toks)
    be.cache.allocate("s", 14)
    _prefill(be, "s", toks[:8], 8)
    worst = 0.0
    for t in range(8, 14):
        pools = be.cache.state_pools
        if fault == "zeroed" and t == 8:
            be.cache.swap_state(tuple(jnp.zeros_like(p) for p in pools))
        before = tuple(jnp.array(p) for p in pools)     # the step donates
        got = _step(be, "s", toks[t], t)
        if fault == "stale" and t == 9:
            be.cache.swap_state(before)     # the step's write is lost
        worst = max(worst, float(np.abs(got - want[t]).max()))
    assert (worst > 1e-3) == (fault != "sound"), worst


# ----------------------------------------------------------------------
# (k) the configuration and its counts


def _published():
    with open(CONFIG) as f:
        return json.load(f)


def _family():
    from benchmark.spec import load_module

    return load_module(os.path.join(ROOT, "benchmark", "models",
                                    "short_conv_moe.py"), "family_scm")


def test_configuration_keeps_the_published_widths():
    """Every value of the catalog's row is in the file under its key,
    but for the one key ``reduced`` names; no width is among them."""
    cfg = _published()
    path = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(path):
        pytest.skip("no catalog here")
    with open(path) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "LFM2-8B-A1B")
    assert cfg["source"] == row["source_url"]
    for key, value in row["config"].items():
        if key in cfg["reduced"]:
            assert cfg[key] != value and cfg["published"][key] == value
        else:
            assert cfg[key] == value, key
    assert cfg["reduced"] == ["num_hidden_layers"] \
        == list(cfg["published"]) == list(cfg["reduced_why"])
    assert cfg["num_hidden_layers"] == 14 and len(cfg["layer_types"]) == 24
    assert cfg["head_dim"] * cfg["num_attention_heads"] == cfg["hidden_size"]
    deployment = cfg["deployment"]
    assert deployment["experts"] == {"published": 32, "held": 32, "first": 0}
    assert deployment["chips_sharing_a_layer"] == 1 \
        and deployment["pipeline_stages"] == 2 \
        and deployment["vocab_shards"] == 1
    program = _family().program_config(cfg)
    kinds = program["layer_types"]
    # the two leading dense layers and three whole periods of three
    # convolution mixers and one attention mixer
    assert "".join(k[0] for k in kinds) == "ccfcccfcccfccc"
    assert [i for i, k in enumerate(row["config"]["layer_types"])
            if k == "full_attention"] == [2, 6, 10, 14, 18, 21]
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        doc = json.load(f)
    entry = next(c for c in doc["configs"] if c["name"] == "lfm2-8b-a1b-pp2")
    assert entry["reduced"] == cfg["reduced"]
    assert entry["source"] == cfg["source"]
    cells = [w for w in doc["workloads"] if w["config"] == entry["name"]]
    assert [w["name"] for w in cells] == ["lfm2-serve-chat64"]
    assert cells[0]["chips"] == 1 and len(cells[0]["why"]) <= 200


def test_parameter_count_of_the_cut_and_of_the_published_model():
    """ISSUE 39's arithmetic: 16.78M a convolution mixer, 10.49M an
    attention mixer, 44.04M a dense feed-forward, 11.01M an expert and
    352.4M a layer's 32 with their router, 134.2M of tied embedding:
    4,667M parameters here, 9.33 GB in bfloat16; and 8,340M for the 24
    layers as published."""
    family = _family()
    cfg = _published()
    count = {k: int(np.prod(s))
             for k, s in family.weight_shapes(cfg).items()}

    def layer(i, *parts):
        return sum(v for k, v in count.items() if k.startswith("l%d_" % i)
                   and k[len("l%d_" % i):].startswith(parts))

    assert layer(0, "conv_") == 6144 * 2048 + 2048 * 2048 + 2048 * 3
    assert abs(layer(0, "conv_") - 16.78e6) < 0.01e6
    assert layer(2, "q_", "k_", "v_", "o_") == 10485760 + 128
    assert layer(0, "ffn_gate", "ffn_up", "ffn_down") == 3 * 2048 * 7168
    assert layer(2, "experts_") == 32 * 3 * 2048 * 1792
    assert abs(layer(2, "experts_", "router_", "expert_bias") - 352.4e6) \
        < 0.05e6
    assert layer(2, "ffn_gate") == 0 and layer(1, "experts_") == 0
    assert count["embed_weight"] == 65536 * 2048 and "pred_weight" not in count
    total = sum(count.values())
    assert abs(total - 4667e6) < 1e6 and abs(2 * total - 9.33e9) < 0.01e9
    whole = dict(cfg, **cfg["published"])
    published = sum(int(np.prod(s))
                    for s in family.weight_shapes(whole).values())
    assert abs(published - 8340e6) < 1e6


def test_a_token_is_6_kb_and_a_sequences_state_88_kb():
    cfg = _family().program_config(_published())
    definition = sc.lm_definition(cfg)
    assert definition.cache_layers == 3 and definition.state.layers == 11
    assert definition.state.rows == (((8, 512), np.dtype(jnp.bfloat16)),)
    assert definition.state.bytes == 11 * 2 * 2048 * 2 == 88 * 1024
    row = definition.cache_row
    assert (row.kind, row.width, row.pools) == ("kv", 512, 2)
    assert row.bytes == 2048 and definition.cache_layers * row.bytes == 6144
    serve = _published()["deployment"]["serve"]
    assert 65536 % serve["checked_logit_parts"] == 0
    assert serve["state_slots"] == 64


def test_seeded_routing_spreads_over_the_experts():
    """The configuration's ``assumed`` weights (normal(0, 0.02) router
    over unit-RMS rows, selection bias normal(0, 0.01)): over 4,096
    seeded rows an expert takes 0.78-1.24 times its even share."""
    rng = jax.random.PRNGKey(5)
    keys = jax.random.split(rng, 3)
    h = jax.random.normal(keys[0], (4096, 2048), jnp.float32)
    router = 0.02 * jax.random.normal(keys[1], (32, 2048), jnp.float32)
    bias = 0.01 * jax.random.normal(keys[2], (32,), jnp.float32)
    chosen, gates = moe.route_group_limited(
        h @ router.T, bias, top_k=4, eps=sc.GATE_SUM_EPS)
    share = np.bincount(np.asarray(chosen).reshape(-1), minlength=32) \
        / (4096 * 4 / 32.0)
    assert share.min() > 0.7 and share.max() < 1.4, share
    np.testing.assert_allclose(gates.sum(1), 1.0, atol=1e-5)


# ----------------------------------------------------------------------
# (m) the benchmark's arithmetic and readers for what this model adds


def test_cost_arithmetic():
    from benchmark import flops
    from benchmark import gated_delta_costs, latent_moe_costs

    cfg = _published()
    assert latent_moe_costs.expert_weight_bytes(cfg) == 22020096
    assert latent_moe_costs.expert_flops_per_assignment(cfg) == 22020096
    # a full decode step of one layer: every expert hit, 256 pairs
    ops, moved = latent_moe_costs.routed_experts_cost(cfg, 32, 256)
    assert ops == 256 * 22020096
    assert moved == 32 * 22020096 + 256 * (2 * 2048 + 3 * 1792) * 2
    peaks = {"flops_per_s": {"bfloat16": 197e12}, "hbm_bytes_per_s": 819e9}
    least, by = flops.roofline_seconds(ops, moved, peaks)
    assert by == "memory" and abs(least - 0.866e-3) < 0.005e-3
    # 64 rows at 1,200 cached tokens each, one layer: 2 KB a token
    ops, moved = gated_delta_costs.gqa_decode_cost(
        cfg, context_tokens=76800, rows=64)
    assert ops == 2 * 76800 * 32 * 2 * 64
    assert moved == (76800 * 2 * 512 + 64 * 32 * 2 * 64) * 2
    assert flops.roofline_seconds(ops, moved, peaks)[1] == "memory"


_NEW_METRICS = ("moe_expert_share.lfm2", "moe_expert_roofline.lfm2",
                "moe_tokens_per_held_expert.lfm2",
                "moe_held_experts_hit_share.lfm2",
                "gqa_paged_decode_roofline.lfm2", "state_mb_per_step.lfm2")


def _trace(events):
    end = max(at + dur for _, at, dur in events)
    return {"window_ns": [0, end], "devices": {"0": events}, "host": []}


def _recorded_events():
    """(name, nanoseconds) of one decode step's operations as a traced
    run of the cell names them (recorded on the chip, PR 39)."""
    with open(os.path.join(ROOT, "benchmark", "data",
                           "lfm2_trace_names.json")) as f:
        return [(e["name"], e["ns"]) for e in json.load(f)["events"]]


def test_readers_of_the_new_metrics(capsys):
    """On the recorded names of one decode step: the shares count what
    their patterns name, the rooflines come out under 100% and say which
    peak bounds them, and every reader returns nothing where there is
    nothing to read (a program without the counters, a run without a
    trace)."""
    from benchmark.spec import Spec

    spec = Spec(ROOT)
    peaks = spec.peaks("TPU v5 lite")

    def read(metric, ctx):
        doc = spec.metric_file(metric)
        return spec.reader(doc["reader"])(ctx, doc.get("params", {}))

    events, at = [], 0
    for name, dur in _recorded_events():
        events.append([name, at, dur])
        at += dur + 1000
    counters = {"generation_state_bytes_total": 100 * 2 * 64 * 90112.0,
                "generation_decode_steps_total": 100.0,
                "generation_decode_context_tokens_total": 100 * 64 * 1200.0,
                "generation_tokens_total": 100 * 64.0,
                "moe_layer_steps_total": 1200.0,
                "moe_local_experts_hit_total": 1200 * 32.0,
                "moe_local_assignments_total": 1200 * 256.0}
    ctx = {"trace": _trace(events), "peaks": peaks,
           "compiles_in_window": counters}
    got = {m: read(m, ctx) for m in _NEW_METRICS}
    out = capsys.readouterr().out
    assert "expert roofline: bound by memory" in out
    assert "gqa decode roofline: bound by memory" in out
    assert got["state_mb_per_step.lfm2"] == pytest.approx(11.534, abs=0.001)
    assert got["moe_tokens_per_held_expert.lfm2"] == 8.0
    assert got["moe_held_experts_hit_share.lfm2"] == 100.0
    assert 0 < got["moe_expert_share.lfm2"] < 100
    for name in ("gqa_paged_decode_roofline.lfm2",
                 "moe_expert_roofline.lfm2"):
        assert 0 < got[name] <= 100, (name, got[name])
    # the parent's program: no such counter, no such operation
    bare = {"trace": _trace([["%fusion.1 = f32[8,8] fusion(%p)", 0, 50]]),
            "peaks": peaks, "compiles_in_window": {
                "generation_decode_steps_total": 100.0}}
    for name in _NEW_METRICS:
        assert read(name, bare) is None, name
        assert read(name, {"peaks": peaks}) is None, name


# ----------------------------------------------------------------------
# (l) the new cell rehearsed through the benchmark's own command, at the
# tiny size on the CPU


@pytest.fixture(scope="module")
def tiny_benchmark(tmp_path_factory):
    """The real BENCHMARK.json cut to the new cell, its configuration
    the tiny one above (the real reference beside it), its traffic a
    few short requests."""
    from benchmark.spec import Spec

    root = tmp_path_factory.mktemp("tiny_benchmark")
    for sub in ("configs", "traffic", "limits"):
        os.makedirs(os.path.join(str(root), sub))
    tiny = copy.deepcopy(TINY)
    tiny["deployment"]["serve"]["state_slots"] = 4
    # the driver is handed a part of each decode row, as in the cell
    tiny["deployment"]["serve"]["checked_logit_parts"] = 5
    with open(os.path.join(str(root), "configs", "tiny-lfm2.json"),
              "w") as f:
        json.dump(tiny, f)
    shutil.copy(REFERENCE, os.path.join(str(root), "configs",
                                        "tiny-lfm2.reference.py"))
    shutil.copy(os.path.join(ROOT, "benchmark", "tests", "tiny",
                             "peaks.json"), str(root))
    with open(os.path.join(ROOT, "benchmark", "traffic",
                           "serve-chat-closed64-5k.json")) as f:
        traffic = json.load(f)
    assert traffic["clients"] == 64 and traffic["decode_buckets"] == [64]
    traffic.update(
        clients=4, requests=8, stagger_s=0.01, max_total_tokens=64,
        prompt_tokens=dict(traffic["prompt_tokens"], median=12, min=4,
                           max=30),
        new_tokens=dict(traffic["new_tokens"], median=6, min=3, max=10),
        prefill_buckets=[16, 32], decode_buckets=[4], traced_seconds=0.3,
        checked_requests=3, request_timeout_s=60)
    with open(os.path.join(str(root), "traffic", "serve-tiny-5k.json"),
              "w") as f:
        json.dump(traffic, f)
    with open(os.path.join(str(root), "limits", "tiny-lfm2-serve.json"),
              "w") as f:
        json.dump({"served_token_logit_gap": 1e-3,
                   "served_logit_abs_err": 1e-3}, f)
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        doc = json.load(f)
    doc["paths"], doc["run_seconds"] = ["."], 1
    doc["configs"] = [dict(c, name="tiny-lfm2", source="test only",
                           file="configs/tiny-lfm2.json")
                      for c in doc["configs"]
                      if c["name"] == "lfm2-8b-a1b-pp2"]
    doc["workloads"] = [dict(w, name="tiny-lfm2-serve", config="tiny-lfm2",
                             traffic="serve-tiny-5k")
                        for w in doc["workloads"]
                        if w["name"] == "lfm2-serve-chat64"]
    for m in doc["end_to_end"] + doc["per_layer"]:
        if "workloads" in m:
            m["workloads"] = ["tiny-lfm2-serve"] \
                if "lfm2-serve-chat64" in m["workloads"] else []
    return Spec(str(root), doc=doc)


@pytest.mark.parametrize("trace", [0, 1])
def test_new_cell_rehearsed_on_the_cpu(tiny_benchmark, trace, capsys):
    from benchmark import run

    result = run.run_cell(tiny_benchmark, "tiny-lfm2-serve",
                          3000000039 + trace, 1.5, trace,
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
        assert 4 < metrics["decode_context_tokens_mean"]["value"] < 64
        assert 0 <= metrics["decode_ahead_share"]["value"] < 100
        # what a step reads and writes of convolution state: at most 4
        # rows of 5 layers of 2 x 32 float32, each way
        per_row = 5 * 2 * 32 * 4 * 2
        assert 0 < metrics["state_mb_per_step.lfm2"]["value"] \
            <= 4 * per_row / 1e6
        # all 8 tiny experts held: 2 pairs a row over a "32" that the
        # metric's file names for the real cell
        assert 0 < metrics["moe_tokens_per_held_expert.lfm2"]["value"] <= 1
        assert 0 < metrics["moe_held_experts_hit_share.lfm2"]["value"]
        # no device trace on a CPU: nothing read, nothing raised
        for name in ("gqa_paged_decode_roofline.lfm2",
                     "moe_expert_roofline.lfm2", "moe_expert_share.lfm2",
                     "device_idle_share.serve"):
            assert name not in metrics
    else:
        assert metrics["serve_tokens_per_s"]["value"] > 0
        # the cell reports no first-token time (PERF.md §6: its median
        # spread 6.1% in one of two sets of six, over half its bound)
        assert "ttft_p50_ms" not in metrics
        assert metrics["setup_s"]["value"] > 0
    json.dumps(result)


def test_the_family_hands_the_driver_a_part_of_each_row(model):
    """Where the configuration gives ``checked_logit_parts`` the backend
    the family builds hands its caller, of every decode row, the part of
    the vocabulary its position names."""
    family = _family()
    tiny = copy.deepcopy(TINY)
    tiny["deployment"]["serve"]["checked_logit_parts"] = 5
    be = family.build_backend(tiny, tiny["deployment"]["serve"], model[1],
                              "scm_kept", lambda base: base)
    be.cache.allocate("s", 8)
    _prefill(be, "s", _tokens(4), 8)
    table = be.cache.block_table("s", be.max_blocks_per_seq)[None]
    out = be.decode([3], [4], table, [5])[0]
    assert out.values.shape == (1, 10) and out[0].part == slice(40, 50)
    assert family.weight_kind("l2_expert_bias") == "bias"
    assert family.weight_kind("l2_q_norm_gamma") == "gain"


def test_reference_one_precision_down_is_not_the_reference(reference):
    """The control of the cell's limits: the reference with every
    operand rounded to float8 (the convolution's too) moves the logits
    by far more than bfloat16 does."""
    cfg = program_config(TINY)
    params = sc.init_params(cfg, 4, jnp.bfloat16, SCALE, BIAS)
    toks = _tokens(16, seed=4)[None]
    exact = np.asarray(reference.logits(TINY, params, toks, "float32"))
    err = {mode: float(np.median(np.abs(np.asarray(
        reference.logits(TINY, params, toks, mode)) - exact)))
        for mode in ("bfloat16", "float8")}
    assert err["float8"] > 3 * err["bfloat16"] > 0, err
    with pytest.raises(ValueError, match="unknown mode"):
        reference.logits(TINY, params, toks, "float16")


def test_unbuilt_variants_are_refused():
    for key, value in (("conv_bias", True), ("use_expert_bias", False),
                       ("rope_scaling", {"factor": 2})):
        with pytest.raises(ValueError, match="not built"):
            sc.lm_config(dict(TINY, **{key: value}), 64)
    with pytest.raises(ValueError, match="layer_types"):
        sc.lm_config(dict(TINY, num_hidden_layers=9), 64)


def test_serve_tool_loads_the_family_by_configuration(tmp_path):
    """``tools/serve.py --lm name=config.json``: the configuration file
    names its family, the family's module builds the backend with both
    kinds of cache."""
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "serve_tool", os.path.join(ROOT, "tools", "serve.py"))
    tool = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(tool)
    path = tmp_path / "tiny.json"
    path.write_text(json.dumps(TINY))
    backend = tool.lm_backend("tiny_tool_scm", "%s:7" % path)
    assert isinstance(backend, serving.LMBackend)
    assert backend.cache.row.kind == "kv" and backend.cache.num_slots == 8
    assert backend.cfg["held"] == (0, 8) and backend.cfg["seq_len"] == 64
    logits, k, v, _, state = backend.prefill(np.zeros(8, np.int32), 3)
    assert logits.shape == (50,) and k.shape == v.shape == (2, 8, 16)
    assert [s.shape for s in state] == [(5, 2, 32)]
