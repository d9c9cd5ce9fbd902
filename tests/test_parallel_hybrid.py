"""The hybrid decoder whose every layer runs a Mamba-2 mixer and a rotary
grouped-query attention side by side (``models/parallel_hybrid.py``) and
what it forced: a layer that keeps a recurrent state *and* a cache row a
token, so that one decode step goes through both stores in every layer;
fourteen published multipliers, each applied where the modeling code
applies it; a feed-forward that runs a long prompt in stretches.

Everything is held against the benchmark's plain reference
(``benchmark/configs/falcon-h1-34b-pp12.reference.py``, which imports
nothing of the program) at a tiny size with the published *structure*:
three layers, 4 state-space heads of 8 channels in 2 groups with a state
of 8, four taps, 10 query heads over 2 key-value heads (five a head, as
published), the published multipliers but ``attention_in_multiplier``
(1 as published: 0.7 here, so that leaving it out is seen).  float32 on
the CPU, so the two sides differ by the order of float32 additions
only.
"""

import copy
import json
import math
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from mxnet_tpu import serving
from mxnet_tpu.models import parallel_hybrid as ph

# what drives a backend by hand and reads a counter is the same for
# every model with a state
from test_gated_delta_moe import _counter, _prefill, _step

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CONFIG = os.path.join(ROOT, "benchmark", "configs",
                      "falcon-h1-34b-pp12.json")
REFERENCE = CONFIG[:-len(".json")] + ".reference.py"
FAMILY = os.path.join(ROOT, "benchmark", "models", "parallel_hybrid.py")


def draw_rule(cfg):
    """The configuration's ``draw.why`` as arithmetic: each matrix at
    the deviation ``g / (sqrt(fan-in) x the multipliers on its
    product)`` that gives its scaled product the deviation ``g`` on a
    unit-RMS input."""
    root = math.sqrt(cfg["hidden_size"])
    into = root * cfg["ssm_in_multiplier"]
    attn = root * cfg["attention_in_multiplier"]
    m = cfg["ssm_multipliers"]
    heads_out = math.sqrt(cfg["num_attention_heads"] * cfg["head_dim"])
    return {
        "embed_weight": 1.0 / cfg["embedding_multiplier"],
        "in_weight.z": 1.0 / (into * m[0]),
        "in_weight.x": 1.0 / (into * m[1]),
        "in_weight.B": 1.0 / (into * m[2]),
        "in_weight.C": 1.0 / (into * m[3]),
        "dt_weight": 1.3 / (into * m[4]),
        "conv_weight": 1.0 / math.sqrt(cfg["mamba_d_conv"]),
        "conv_bias": 0.1,
        "out_weight": 0.3 / (math.sqrt(cfg["mamba_d_ssm"])
                             * cfg["ssm_out_multiplier"]),
        "q_weight": math.sqrt(2) / attn,
        "k_weight": math.sqrt(2) / (attn * cfg["key_multiplier"]),
        "v_weight": 1.0 / attn,
        "o_weight": 1.5 / (heads_out * cfg["attention_out_multiplier"]),
        "gate_weight": 1.0 / (root * cfg["mlp_multipliers"][0]),
        "up_weight": 1.0 / root,
        "down_weight": 0.5 / (math.sqrt(cfg["intermediate_size"])
                              * cfg["mlp_multipliers"][1]),
        "pred_weight": 1.5 / (root * cfg["lm_head_multiplier"])}


def _tiny():
    with open(CONFIG) as f:
        published = json.load(f)
    tiny = {k: published[k] for k in (
        "family", "attention_bias", "attn_layer_indices", "hidden_act",
        "mamba_conv_bias", "mamba_norm_before_gate", "mamba_proj_bias",
        "mamba_rms_norm", "mlp_bias", "projectors_bias", "rms_norm_eps",
        "rope_scaling", "rope_theta") + ph.MULTIPLIERS}
    tiny.update(
        hidden_size=32, num_hidden_layers=3, num_attention_heads=10,
        num_key_value_heads=2, head_dim=8, intermediate_size=48,
        mamba_d_ssm=32, mamba_n_heads=4, mamba_d_head=8, mamba_d_state=8,
        mamba_n_groups=2, mamba_d_conv=4, mamba_chunk_size=8, vocab_size=64,
        n_positions=64, attention_in_multiplier=0.7,
        deployment={"serve": {"dtype": "float32", "block_size": 4,
                              "num_blocks": 256, "state_slots": 8}})
    tiny["draw"] = {"deviation": draw_rule(tiny)}
    return tiny


# the benchmark's configuration file at the tiny size
TINY = _tiny()
# what the two float32 sides may differ by, on logits of deviation 1.5
TOL = 2e-4


@pytest.fixture(scope="module")
def reference():
    from benchmark.spec import load_module

    return load_module(REFERENCE, "reference_falcon")


@pytest.fixture(scope="module")
def family():
    from benchmark.spec import load_module

    return load_module(FAMILY, "family_parallel_hybrid")


def make_params(family, cfg=TINY, seed=0):
    """The benchmark's own draw (each kind at its deviation) at the tiny
    size, float32."""
    return family.make_weights(cfg, seed)


@pytest.fixture(scope="module")
def model(family):
    return family.program_config(TINY), make_params(family)


def _tokens(n, seed=0):
    return np.random.RandomState(seed).randint(
        0, TINY["vocab_size"], n).astype(np.int32)


def _backend(model, name, **kw):
    cfg, params = model
    kw.setdefault("num_blocks", 64)
    return serving.LMBackend(
        params, definition=ph.lm_definition(cfg, jnp.float32), block_size=4,
        model=name, state_slots=kw.pop("state_slots", 4), **kw)


def _reference_logits(reference, params, toks, cfg=TINY, **kw):
    return np.asarray(jax.jit(lambda p, t: reference.logits(
        cfg, p, t, "float32", **kw))(
            params, np.asarray(toks, np.int32)[None]))[0]


def _program_logits(params, toks, cfg):
    return np.asarray(jax.jit(lambda p, t: ph.full_logits(p, t, cfg))(
        params, np.asarray(toks, np.int32)[None]))[0]


# ----------------------------------------------------------------------
# (a) a layer and the full forward, (b) prefill then decode through both
# stores


def test_a_layer_is_the_reference(reference, model):
    """One layer of the residual stream: both mixers read the one
    normed input and their outputs are summed into one update, then the
    gated feed-forward; the state and the cache rows it hands on have
    the shapes the stores keep."""
    cfg, params = model
    x = jnp.asarray(np.random.RandomState(2).randn(24, 32), jnp.float32)

    def program(params, x):
        h_ssm, h_attn = ph._branch_inputs(params, "l1_", x, cfg)
        mamba, state, tail = ph._sm._mamba_prefill(params, "l1_", h_ssm,
                                                   None, cfg)
        attention, k, v = ph._attention_prefill(
            params, "l1_", h_attn, jnp.arange(24, dtype=jnp.int32), cfg)
        out = ph._feed_forward(params, "l1_",
                               ph._mixed(x, mamba, attention, cfg), cfg)
        return out, state, tail, k, v

    got, state, tail, k, v = jax.jit(program)(params, x)
    want = jax.jit(lambda p, x: reference.layer(
        TINY, reference._layer_weights(p, 1), x,
        reference._Math("float32")))(params, x)
    assert float(jnp.abs(want - x).max()) > 0.5
    np.testing.assert_allclose(got, want, atol=TOL, rtol=0)
    assert state.shape == (2, 8, 16) and tail.shape == (3, 64)
    assert k.shape == v.shape == (24, 16)


def test_full_forward_is_the_reference(reference, model):
    cfg, params = model
    definition = ph.lm_definition(cfg, jnp.float32)
    # every layer keeps a row a token and a state a sequence
    assert (definition.cache_layers, definition.state.layers) == (3, 3)
    toks = _tokens(40, 3)
    want = _reference_logits(reference, params, toks)
    got = _program_logits(params, toks, cfg)
    assert 1.0 < want.std() < 2.0
    np.testing.assert_allclose(got, want, atol=TOL, rtol=0)


@pytest.mark.parametrize("run_ahead", [False, True], ids=["alone", "ahead"])
@pytest.mark.parametrize("bucket", [5, 8, 32])
def test_prefill_then_decode_through_both_stores_is_the_reference(
        model, reference, bucket, run_ahead):
    """A 5-token prompt at three bucket paddings, then 15 greedy decode
    steps through ``LMBackend``: every layer's attention through the
    paged key and value pools *and* every layer's mixer through the
    state pool, in the same step.  Every step's logits against the
    reference's one forward over all 20 tokens (logits, not tokens:
    TOL is the order of float32 additions); with run-ahead every call
    but the first is answered by the step queued behind the one before
    it."""
    be = _backend(model, "ph_b%d%d" % (bucket, run_ahead))
    assert be.cache.k_pages.shape == (3, 64, 4, 16)     # every layer cached
    # and every layer a state: two versions of four slots, the pad rows'
    # row: the state [2 groups, 8, 16 channels] and 3 tail rows of 64
    assert [p.shape for p in be.cache.state_pools] == [
        (3 * 2 * 4 + 1, 2, 8, 16), (3 * 2 * 4 + 1, 3, 64)]
    prompt = _tokens(5, 7)
    be.cache.allocate("s", 20)
    got = [_prefill(be, "s", prompt, bucket)]
    toks = list(prompt)
    for t in range(5, 20):
        toks.append(int(np.argmax(got[-1])))
        got.append(_step(be, "s", toks[-1], t, run_ahead and t < 19))
    want = _reference_logits(reference, model[1], toks)
    np.testing.assert_allclose(np.stack(got), want[4:], atol=TOL, rtol=0)
    assert _counter("generation_decode_ahead_used_total", model=be.model) \
        == (14 if run_ahead else 0)
    # every step moved one row's state once each way: 3 layers of 256
    # float32 values of state and 192 of tail
    assert _counter("generation_state_bytes_total", model=be.model) \
        == 15 * 2 * 3 * (256 + 192) * 4
    assert _counter("serving_state_slots_used", model=be.model) == 1
    assert _counter("kv_cache_layers", model=be.model) == 3
    # the family books the scan's counts under its own label: 5 tokens
    # through 3 layers, XLA's body running every chunk of 8 of the bucket
    assert _counter("ssm_prefill_tokens_total", model=be.model) == 15
    assert _counter("ssm_prefill_chunks_run_total", model=be.model) \
        == 3 * -(-bucket // 8)
    assert _counter("ssm_prefill_chunks_skipped_total", model=be.model) == 0
    assert _counter("ssm_prefill_chunk_tokens", tokens=str(bucket),
                    form="xla") == 8


def test_two_sequences_keep_their_own_rows_and_states(model, reference):
    """Two sequences of different lengths in one decode batch: each
    row's logits are its own sequence's."""
    be = _backend(model, "ph_two")
    seqs = {"a": list(_tokens(6, 21)), "b": list(_tokens(11, 22))}
    for name, toks in seqs.items():
        be.cache.allocate(name, len(toks) + 4)
        _prefill(be, name, np.asarray(toks[:-1], np.int32), 16)
    for _ in range(4):
        names = sorted(seqs)
        tables = np.stack([be.cache.block_table(n, be.max_blocks_per_seq)
                           for n in names])
        positions = [len(seqs[n]) - 1 for n in names]
        logits = be.decode([seqs[n][-1] for n in names], positions, tables,
                           [p + 1 for p in positions])[0]
        for row, name in enumerate(names):
            want = _reference_logits(reference, model[1], seqs[name])
            np.testing.assert_allclose(logits[row], want[-1], atol=TOL,
                                       rtol=0)
            seqs[name].append(int(np.argmax(logits[row])))


# ----------------------------------------------------------------------
# the fourteen multipliers


_FOURTEEN = [("embedding_multiplier", None), ("lm_head_multiplier", None),
             ("attention_in_multiplier", None),
             ("attention_out_multiplier", None), ("key_multiplier", None),
             ("ssm_in_multiplier", None), ("ssm_out_multiplier", None)] \
    + [("ssm_multipliers", i) for i in range(5)] \
    + [("mlp_multipliers", i) for i in range(2)]


@pytest.mark.parametrize("key,index", _FOURTEEN, ids=[
    k if i is None else "%s-%d" % (k, i) for k, i in _FOURTEEN])
def test_every_multiplier_is_applied(reference, model, key, index):
    """With any one of the fourteen changed by a quarter in the program
    alone, the full forward and a decode step leave the reference by a
    thousand times the tolerance they are held to; unchanged, they are
    within it (the tests above)."""
    assert len(_FOURTEEN) == 14
    changed = copy.deepcopy(TINY)
    if index is None:
        changed[key] = changed[key] * 1.25
    else:
        changed[key] = list(changed[key])
        changed[key][index] *= 1.25
    cfg = ph.lm_config(changed, 64)
    toks = _tokens(20, 5)
    want = _reference_logits(reference, model[1], toks)
    got = _program_logits(model[1], toks, cfg)
    assert np.abs(got - want).max() > 100 * TOL, key
    # and through the served path: prefill, then one decode step
    be = serving.LMBackend(
        model[1], definition=ph.lm_definition(cfg, jnp.float32),
        block_size=4, num_blocks=32, state_slots=2,
        model="ph_m_%s%s" % (key, index))
    be.cache.allocate("s", 20)
    first = _prefill(be, "s", toks[:19], 32)
    step = _step(be, "s", toks[19], 19)
    assert max(np.abs(first - want[18]).max(),
               np.abs(step - want[19]).max()) > 100 * TOL, key


def test_no_multiplier_is_folded_into_a_matrix(model):
    """The program's weights are the checkpoint's: what
    ``lm_definition`` serves is the dict it was handed (``prepare`` is
    None), and the scales the mixer's projection takes are float32
    constants beside it."""
    cfg, _ = model
    assert ph.lm_definition(cfg, jnp.float32).prepare is None
    rows, dt = cfg["in_proj_scales"]
    assert rows.dtype == np.float32 and rows.shape == (32 + 32 + 16 + 16,)
    m = TINY["ssm_multipliers"]
    np.testing.assert_allclose(
        rows[[0, 32, 64, 80]], np.asarray(m[:4], np.float32))
    assert dt == np.float32(m[4])


# ----------------------------------------------------------------------
# the seeded draw


def test_published_deviations_follow_the_rule():
    with open(CONFIG) as f:
        published = json.load(f)
    table = published["draw"]["deviation"]
    rule = draw_rule(dict(published, **published["published"]))
    assert sorted(table) == sorted(rule)
    for kind, value in rule.items():
        assert table[kind] == pytest.approx(value, rel=1e-3), kind


def test_every_branch_moves_the_residual_and_the_logits_spread(reference,
                                                               family):
    """The rule of the configuration's ``assumed`` at the tiny size,
    over 256 tokens: in every layer each of the three branches' updates
    has an RMS of 0.05-1 of the residual's, and the logits a deviation
    of 1-2: the comparison sees every branch of every layer."""
    params = make_params(family, seed=3)
    toks = jnp.asarray(_tokens(256, 9))
    parts = []
    hidden = reference.hidden(TINY, params, toks, parts=parts)
    logits = reference.head(TINY, params, hidden)

    def rms(a):
        return float(jnp.sqrt(jnp.mean(jnp.square(a))))

    assert len(parts) == 3
    for seen in parts:
        residual = rms(seen["residual"])
        for branch in ("mamba", "attention"):
            assert 0.05 < rms(seen[branch]) / residual < 1.0, branch
        assert 0.05 < rms(seen["feed_forward"]) / rms(seen["mixed"]) < 1.0
    assert 1.0 < float(jnp.std(logits)) < 2.0
    # a token's decay spans about 0.6-0.999 across heads
    a = np.exp(np.concatenate([np.asarray(params["l%d_A_log" % i])
                               for i in range(3)]))
    assert 0.001 <= a.min() and a.max() <= 0.7


# ----------------------------------------------------------------------
# the counts at the published sizes, the refusals, the stretches


def test_parameter_count_at_the_published_sizes():
    """ISSUE 48's arithmetic from shapes alone: 430.1M a layer (47.35M
    in_proj, 20.97M out_proj, 31.46M attention, 330.30M feed-forward),
    1,336.9M each of embedding and head, 33.64B for the 72 layers as
    published, and 10.51 GB in bfloat16 for the six built."""
    with open(CONFIG) as f:
        published = json.load(f)
    whole = ph.lm_config(dict(published, **published["published"]), 10240)
    count = {k: int(np.prod(s)) for k, s in ph.param_shapes(whole).items()}

    def layer(*parts):
        return sum(v for k, v in count.items()
                   if k.startswith("l0_") and k[3:].startswith(parts))

    assert layer("in_weight", "dt_weight") == 9248 * 5120
    assert layer("out_weight") == 5120 * 4096
    assert layer("q_", "k_", "v_", "o_") == 2 * 5120 * 2560 + 2 * 5120 * 512
    assert layer("gate_", "up_", "down_") == 3 * 5120 * 21504
    assert abs(layer("") - 430.1e6) < 0.05e6
    assert count["embed_weight"] == count["pred_weight"] == 261120 * 5120
    assert abs(sum(count.values()) - 33.64e9) < 0.005e9
    built = ph.lm_config(published, 10240)
    here = sum(int(np.prod(s)) for s in ph.param_shapes(built).values())
    assert abs(2 * here - 10.51e9) < 0.005e9
    # a sequence's state a version and a token's rows, over six layers
    definition = ph.lm_definition(built)
    assert definition.state.rows == (
        ((2, 256, 2048), np.dtype(np.float32)),
        ((30, 512), np.dtype(jnp.bfloat16)))
    assert definition.state.bytes == 6 * (4194304 + 30720)
    assert 6 * definition.cache_row.bytes == 12288


def test_unbuilt_variants_are_refused():
    for key, value in (("mamba_rms_norm", False), ("projectors_bias", True),
                       ("mamba_proj_bias", True), ("attention_bias", True),
                       ("mamba_norm_before_gate", True),
                       ("attn_layer_indices", [0, 2]),
                       ("rope_scaling", {"type": "linear", "factor": 2}),
                       ("mamba_conv_bias", False), ("hidden_act", "gelu")):
        with pytest.raises(ValueError, match="not built"):
            ph.lm_config(dict(TINY, **{key: value}), 64)
    with pytest.raises(ValueError, match="mamba_d_ssm"):
        ph.lm_config(dict(TINY, mamba_d_ssm=80), 64)
    with pytest.raises(ValueError, match="five ssm_multipliers"):
        ph.lm_config(dict(TINY, mlp_multipliers=[1.0]), 64)


def test_the_feed_forward_in_stretches_is_the_feed_forward_whole(
        model, monkeypatch):
    """A prompt longer than ``FF_SEGMENT`` runs its feed-forward as
    stretches of one loop: the same logits, state and rows."""
    cfg, params = model
    assert [ph._ff_segment(n) for n in (512, 1536, 3072, 4096, 6144, 8192)] \
        == [512, 768, 1024, 1024, 1024, 1024]
    toks = jnp.asarray(np.pad(_tokens(40, 4), (0, 24)))
    run = jax.jit(lambda p, t: ph.prefill(p, t, 40, cfg))
    whole = run(params, toks)
    monkeypatch.setattr(ph, "FF_SEGMENT", 16)
    monkeypatch.setattr(ph, "_ff_segment", lambda n: 16)
    cut = jax.jit(lambda p, t: ph.prefill(p, t, 40, cfg))(params, toks)
    for a, b in zip(jax.tree_util.tree_leaves(whole),
                    jax.tree_util.tree_leaves(cut)):
        np.testing.assert_allclose(a, b, atol=1e-5, rtol=1e-5)


# ----------------------------------------------------------------------
# the controls the limits are held to: a lost state, a dropped branch


@pytest.mark.parametrize("fault", ["sound", "zeroed", "no_attention"])
def test_a_lost_state_and_a_dropped_branch_fail_the_tiny_limits(
        model, reference, fault):
    """What the cell's ``correct`` has to see: the state zeroed at the
    hand-over from prefill to decode, or the attention branch left out
    of every layer (a server that answers from the state alone), moves
    the served logits past the limit the tiny cell runs under (1e-3);
    left alone they are within it.  The reference's own controls
    (``lost_at``, ``attention=False``) read what the faulty programs
    read."""
    cfg, params = model
    if fault == "no_attention":
        cfg = ph.lm_config(dict(TINY, attention_out_multiplier=0.0), 64)
    be = _backend((cfg, params), "ph_fault_" + fault)
    toks = _tokens(14, 13)
    want = _reference_logits(reference, params, toks)
    be.cache.allocate("s", 14)
    _prefill(be, "s", toks[:8], 8)
    if fault == "zeroed":
        be.cache.swap_state(tuple(jnp.zeros_like(p)
                                  for p in be.cache.state_pools))
    got = np.stack([_step(be, "s", toks[t], t) for t in range(8, 14)])
    worst = float(np.abs(got - want[8:]).max())
    assert (worst > 1e-3) == (fault != "sound"), worst
    if fault == "zeroed":
        lost = _reference_logits(reference, params, toks, lost_at=8)
        np.testing.assert_allclose(got, lost[8:], atol=TOL, rtol=0)
        np.testing.assert_array_equal(lost[:8], want[:8])
    if fault == "no_attention":
        dropped = _reference_logits(reference, params, toks,
                                    attention=False)
        np.testing.assert_allclose(got, dropped[8:], atol=TOL, rtol=0)
        assert np.abs(dropped - want).max() > 0.1


def test_reference_one_precision_down_is_not_the_reference(reference,
                                                           family):
    """The third control: the reference with every operand rounded to
    float8 (the recurrence's with its state too) moves the logits by far
    more than bfloat16 does."""
    served = dict(TINY, deployment={"serve": {"dtype": "bfloat16"}})
    params = make_params(family, served, seed=4)
    assert params["l0_in_weight"].dtype == jnp.bfloat16
    assert params["l0_A_log"].dtype == jnp.float32
    toks = _tokens(16, seed=4)[None]

    def run(mode):
        return np.asarray(jax.jit(lambda p, t: reference.logits(
            TINY, p, t, mode))(params, toks))

    exact = run("float32")
    err = {mode: float(np.median(np.abs(run(mode) - exact)))
           for mode in ("bfloat16", "float8")}
    assert err["float8"] > 3 * err["bfloat16"] > 0, err
    with pytest.raises(ValueError, match="unknown mode"):
        reference.logits(TINY, params, toks, "float16")
