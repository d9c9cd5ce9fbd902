"""The hybrid decoder of Mamba-2 state-space layers, attention without
positions and experts of two matrices in a latent
(``models/state_space_moe.py``) and what it forced: the selective
state-space operator in its two forms (``ops/state_space.py``), a state
pool whose rows are a 4 MB float32 state and a convolution's tail, the
two-matrix ``relu^2`` expert in every form of the dropless layer, a
layer pattern whose cached layers, state layers and expert layers are
three different subsets of the depth, and a Mamba-2 layer that runs a
long prompt as stretches which hand state and tail on.

Everything is held against the benchmark's plain reference
(``benchmark/configs/nemotron3-super-ep4.reference.py``, which imports
nothing of the program) at a tiny size with the published *structure*:
seven layers ``M E * M E M E`` of a longer pattern string, 4 state-space
heads of 8 channels in 2 groups with a state of 8, four taps, 4 query
heads over 2 key-value heads, 8 experts of which 3 a token in a latent
of 16.  float32 on the CPU, so the two sides differ by the order of
float32 additions only.
"""

import copy
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from mxnet_tpu import serving
from mxnet_tpu.models import state_space_moe as sm
from mxnet_tpu.ops import platform, short_conv, state_space
from mxnet_tpu.parallel import moe

# what drives a backend by hand and reads a counter is the same for
# every model with a state
from test_gated_delta_moe import _counter, _prefill, _step

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CONFIG = os.path.join(ROOT, "benchmark", "configs",
                      "nemotron3-super-ep4.json")
REFERENCE = CONFIG[:-len(".json")] + ".reference.py"
# the benchmark's configuration file at the tiny size: the published
# keys, the experts held (all 8 here), the deployment
TINY = {
    "family": "state_space_moe", "hidden_size": 32, "num_hidden_layers": 7,
    "hybrid_override_pattern": "ME*MEMEM*E", "num_attention_heads": 4,
    "num_key_value_heads": 2, "head_dim": 8, "mamba_num_heads": 4,
    "mamba_head_dim": 8, "ssm_state_size": 8, "n_groups": 2,
    "conv_kernel": 4, "chunk_size": 8, "n_routed_experts": 8,
    "num_experts_per_tok": 3, "moe_intermediate_size": 24,
    "moe_latent_size": 16, "moe_shared_expert_intermediate_size": 40,
    "routed_scaling_factor": 2.5, "norm_topk_prob": True, "n_group": 1,
    "topk_group": 1, "norm_eps": 1e-5, "use_bias": False, "mlp_bias": False,
    "attention_bias": False, "mamba_proj_bias": False,
    "use_conv_bias": True, "mlp_hidden_act": "relu2",
    "mamba_hidden_act": "silu", "n_shared_experts": 1, "vocab_size": 50,
    "n_positions": 64,
    "deployment": {"experts": {"published": 8, "held": 8, "first": 0},
                   "serve": {"dtype": "float32", "block_size": 4,
                             "num_blocks": 256, "state_slots": 8}}}
# 0.3-wide weights and a 0.1-wide selection bias: large enough that the
# experts, the state, the gates and the convolution all move the logits
SCALE, BIAS = 0.3, 0.1
# what the two float32 sides may differ by, on logits of size ~5
TOL = 2e-4


def held_config(first=0, count=8):
    cfg = copy.deepcopy(TINY)
    cfg["n_routed_experts"] = count
    cfg["deployment"]["experts"].update(held=count, first=first)
    return cfg


def program_config(cfg):
    share = cfg["deployment"]["experts"]
    return sm.lm_config(dict(cfg, n_routed_experts=share["published"]),
                        seq_len=cfg["n_positions"],
                        held=(share["first"], share["held"]))


@pytest.fixture(scope="module")
def reference():
    from benchmark.spec import load_module

    return load_module(REFERENCE, "reference_nemotron")


@pytest.fixture(scope="module")
def model():
    cfg = program_config(TINY)
    return cfg, sm.init_params(cfg, 0, jnp.float32, SCALE, BIAS)


def _tokens(n, seed=0):
    return np.random.RandomState(seed).randint(
        0, TINY["vocab_size"], n).astype(np.int32)


def _backend(model, name, **kw):
    cfg, params = model
    kw.setdefault("num_blocks", 64)
    return serving.LMBackend(
        params, definition=sm.lm_definition(cfg, jnp.float32), block_size=4,
        model=name, state_slots=kw.pop("state_slots", 4), **kw)


def _reference_logits(reference, params, toks):
    return np.asarray(jax.jit(lambda p, t: reference.logits(TINY, p, t))(
        params, np.asarray(toks, np.int32)[None]))[0]


# ----------------------------------------------------------------------
# (a) the full forward, (b) prefill then decode through state and cache


def test_full_forward_is_the_reference_on_a_share(reference):
    """With a share of the experts held (ids 2-5 of 8): the reference
    leaves out what the absent four would add, as the program does; the
    three kinds of layer are three subsets of the depth."""
    tiny = held_config(first=2, count=4)
    cfg = program_config(tiny)
    assert cfg["held"] == (2, 4)
    assert cfg["layer_kinds"] == ("M", "E", "*", "M", "E", "M", "E")
    params = sm.init_params(cfg, 1, jnp.float32, SCALE, BIAS)
    assert params["l1_experts_up_weight"].shape == (4, 16, 24)
    assert params["l1_experts_down_weight"].shape == (4, 24, 16)
    assert "l1_experts_gate_weight" not in params
    assert "l0_router_weight" not in params and "l2_q_weight" in params
    definition = sm.lm_definition(cfg, jnp.float32)
    assert (definition.cache_layers, definition.state.layers) == (1, 3)
    toks = _tokens(24, 3)
    want = np.asarray(jax.jit(lambda p, t: reference.logits(tiny, p, t))(
        params, toks[None]))[0]
    got = np.asarray(jax.jit(lambda p, t: sm.full_logits(p, t, cfg))(
        params, toks[None]))[0]
    assert np.abs(want).max() > 1.0
    np.testing.assert_allclose(got, want, atol=TOL, rtol=0)


@pytest.mark.parametrize("run_ahead", [False, True], ids=["alone", "ahead"])
@pytest.mark.parametrize("bucket", [5, 8, 16, 32])
def test_prefill_then_decode_through_state_and_cache_is_the_reference(
        model, reference, bucket, run_ahead):
    """A 5-token prompt at every bucket padding, then 15 greedy decode
    steps through ``LMBackend``: the attention layer through the paged
    key and value pools, the state-space layers through the state pool.
    Every step's logits against the reference's one forward over all 20
    tokens; with run-ahead every call but the first is answered by the
    step queued behind the one before it."""
    be = _backend(model, "ssm_b%d%d" % (bucket, run_ahead))
    assert be.cache.k_pages.shape == (1, 64, 4, 16)     # one cached layer
    # three state layers, two versions of four slots, the pad rows' row:
    # the state [2 groups, 8, 16 channels] and 3 tail rows of 64 channels
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
    used = _counter("generation_decode_ahead_used_total", model=be.model)
    assert used == (14 if run_ahead else 0)
    # every step moved one row's state once each way: 3 layers of 256
    # float32 values of state and 192 of tail
    assert _counter("generation_state_bytes_total", model=be.model) \
        == 15 * 2 * 3 * (256 + 192) * 4
    assert _counter("serving_state_slots_used", model=be.model) == 1
    assert _counter("kv_cache_layers", model=be.model) == 1
    # the prefill scanned its 5 tokens through 3 state layers
    assert _counter("ssm_prefill_tokens_total", model=be.model) == 15
    # XLA's body runs every chunk of 8 of the bucket, pad or not
    assert _counter("ssm_prefill_chunks_run_total", model=be.model) \
        == 3 * -(-bucket // 8)
    assert _counter("ssm_prefill_chunks_skipped_total", model=be.model) == 0
    assert _counter("moe_layer_steps_total", model=be.model) == 3 * 16


# ----------------------------------------------------------------------
# (c) the operator's forms


def _scan_inputs(t, seed=0, heads=4, p=8, groups=2, n=8):
    rng = np.random.RandomState(seed)

    def rand(*shape):
        return jnp.asarray(rng.randn(*shape), jnp.float32)

    return dict(x=rand(t, heads, p), dt=jax.nn.softplus(rand(t, heads)),
                a_rate=-jnp.exp(rand(heads)), b=rand(t, groups, n),
                c=rand(t, groups, n), d_skip=rand(heads))


def _as_reference_state(state, heads, p):
    """``[G, N, W]`` as the reference's ``[H, P, N]``."""
    g, n, _ = state.shape
    return state.reshape(g, n, heads // g, p).transpose(0, 2, 3, 1).reshape(
        heads, p, n)


@pytest.mark.parametrize("carried", [False, True], ids=["empty", "carried"])
@pytest.mark.parametrize("chunk,block", [(8, 4), (16, 1), (5, 3)])
def test_chunked_scan_is_the_plain_scan_and_the_repeated_step(
        reference, carried, chunk, block):
    """37 tokens: the prefill's form (chunks that divide nothing evenly
    among them), the reference's scan a token and the one-token update
    repeated give the same outputs and the same final state, from an
    empty state and from a carried-in one."""
    v = _scan_inputs(37, seed=chunk)
    start = jnp.asarray(np.random.RandomState(9).randn(2, 8, 16),
                        jnp.float32) if carried else None
    y, state = jax.jit(lambda s: state_space.ssm_chunked(
        v["x"], v["dt"], v["a_rate"], v["b"], v["c"], v["d_skip"], s,
        chunk=chunk, block=block))(start)
    want, want_state = jax.jit(lambda s: reference.selective_scan(
        v["x"], v["dt"], v["a_rate"], v["b"], v["c"], v["d_skip"],
        state=s))(None if start is None
                  else _as_reference_state(start, 4, 8))
    np.testing.assert_allclose(y, want, atol=2e-5, rtol=1e-5)
    np.testing.assert_allclose(_as_reference_state(state, 4, 8), want_state,
                               atol=2e-5, rtol=1e-5)

    def one(s, t):
        out, s = state_space.ssm_step(
            v["x"][t][None], v["dt"][t][None], v["a_rate"], v["b"][t][None],
            v["c"][t][None], v["d_skip"], s)
        return s, out[0]

    s0 = jnp.zeros((1, 2, 8, 16)) if start is None else start[None]
    last, steps = jax.jit(lambda s: jax.lax.scan(one, s, jnp.arange(37)))(s0)
    np.testing.assert_allclose(steps, want, atol=2e-5, rtol=1e-5)
    np.testing.assert_allclose(last[0], state, atol=2e-5, rtol=1e-5)


@pytest.mark.parametrize("length", [1, 7, 8, 19])
def test_pad_positions_leave_the_state_as_at_length(length):
    """Positions from ``length`` on pass with ``dt = 0``: the state the
    scan hands back is the one a scan of the first ``length`` tokens
    gives, whatever lies behind them."""
    v = _scan_inputs(24, seed=length)
    args = ("x", "dt", "a_rate", "b", "c", "d_skip")
    padded = jax.jit(lambda n: state_space.ssm_chunked(
        *(v[k] for k in args), length=n, chunk=8, block=2))(length)
    cut = [v[k][:length] if k in ("x", "dt", "b", "c") else v[k]
           for k in args]
    alone = jax.jit(lambda: state_space.ssm_chunked(*cut, chunk=8,
                                                    block=2))()
    np.testing.assert_allclose(padded[1], alone[1], atol=1e-6, rtol=1e-6)
    np.testing.assert_allclose(padded[0][:length], alone[0], atol=1e-6,
                               rtol=1e-6)


# the prefill scan's kernel under the interpreter, at the smallest whole
# tiles: 2 groups of 2 heads of 64 (W = 128), a state of 128, chunks of
# 128
_KERNEL = dict(heads=4, p=64, groups=2, n=128)
_OPERANDS = ("x", "dt", "a_rate", "b", "c", "d_skip")


@pytest.fixture
def interpreted(monkeypatch):
    monkeypatch.setattr(platform, "pallas_mode", lambda: "interpret")
    jax.clear_caches()
    yield
    jax.clear_caches()


# the parallel hybrid's shape (models/parallel_hybrid.py): a head fills
# 128 lanes by itself, the state is 256 a channel
_KERNEL_WIDE = dict(heads=2, p=128, groups=2, n=256)


def _kernel_inputs(t, seed, dtype=jnp.float32, shape=_KERNEL):
    """B and C a tenth as wide, so that a sum over a state of 128 stays
    of size one and the float32 tolerance means what it means above."""
    v = _scan_inputs(t, seed, **shape)
    return dict(v, x=v["x"].astype(dtype), b=(0.1 * v["b"]).astype(dtype),
                c=(0.1 * v["c"]).astype(dtype))


def _kernel_state(seed=9, shape=_KERNEL):
    return jnp.asarray(np.random.RandomState(seed).randn(
        *state_space.state_shape(shape["heads"], shape["p"],
                                 shape["groups"], shape["n"])), jnp.float32)


def _scan(v, **kw):
    return jax.jit(lambda s, n: state_space.ssm_chunked(
        *(v[k] for k in _OPERANDS), state=s, length=n, chunk=128))(
        kw.get("state"), kw.get("length"))


@pytest.mark.parametrize("dtype,shape", [
    ("float32", _KERNEL), ("bfloat16", _KERNEL), ("bfloat16", _KERNEL_WIDE)],
    ids=["float32", "bfloat16", "bfloat16-a-head-a-tile"])
@pytest.mark.parametrize("carried", [False, True], ids=["empty", "carried"])
def test_scan_kernel_is_the_plain_scan_and_xlas_body(reference, interpreted,
                                                     carried, dtype, shape):
    """300 tokens (two chunks and 44 of a third) through the kernel:
    the outputs and the final state of the reference's scan a token and
    of XLA's body, from an empty state and from a carried-in one, with
    ``x``, ``B`` and ``C`` in float32 (every product of six passes) and
    in bfloat16 (one pass and three; ``y`` is rounded to bfloat16 once,
    so it is held to bfloat16's spacing there, the state to float32's
    in both); at two heads of 64 channels to 128 lanes and at a head of
    128 that fills them by itself over a state of 256 (PR 48: the
    chip's compiler refused the head's decay spread over sublanes and
    lanes at once)."""
    heads, p = shape["heads"], shape["p"]
    v = _kernel_inputs(300, 3, jnp.dtype(dtype), shape)
    start = _kernel_state(shape=shape) if carried else None
    assert state_space.scan_form(4, 64, 2, 128, 128) == "kernel"
    assert state_space.scan_form(32, 128, 2, 256, 128) == "kernel"
    assert state_space.scan_form(4, 8, 2, 8, 8) == "xla"
    assert str(jax.make_jaxpr(lambda: state_space.ssm_chunked(
        *(v[k] for k in _OPERANDS), chunk=128))()).count("pallas_call") == 1
    assert _counter("ssm_prefill_chunk_tokens", tokens="300",
                    form="kernel") == 128
    y, state = _scan(v, state=start)
    assert y.dtype == v["x"].dtype and state.dtype == jnp.float32
    f32 = {k: a.astype(jnp.float32) for k, a in v.items()}
    want, want_state = jax.jit(lambda s: reference.selective_scan(
        *(f32[k] for k in _OPERANDS), state=s))(
        None if start is None else _as_reference_state(start, heads, p))
    xla, xla_state = jax.jit(lambda s: state_space._chunked(
        *(v[k] for k in _OPERANDS), s, None, 128, 4))(start)
    assert np.abs(want).max() > 1.0
    tol = dict(atol=2e-5, rtol=1e-5)
    y_tol = tol if dtype == "float32" else dict(atol=2e-5, rtol=2.0 ** -8)
    np.testing.assert_allclose(y.astype(jnp.float32), want, **y_tol)
    # two roundings of nearly the same sum may fall a spacing apart
    np.testing.assert_allclose(
        y.astype(jnp.float32), xla.astype(jnp.float32),
        **(tol if dtype == "float32" else dict(atol=2e-5, rtol=2.0 ** -7)))
    np.testing.assert_allclose(_as_reference_state(state, heads, p),
                               want_state, **tol)
    np.testing.assert_allclose(state, xla_state, **tol)


@pytest.mark.parametrize("length", [1, 200, 256, 384])
def test_scan_kernel_passes_the_pad_and_skips_its_chunks(interpreted,
                                                         length):
    """A bucket of three chunks with ``length`` at its first token, in
    the middle of a chunk, on a chunk's edge and at its end: the state
    is the one a scan of the first ``length`` tokens gives, the outputs
    before ``length`` are that scan's, and the chunks that lie wholly
    behind it ran no product: their rows of ``y`` are ``D x``."""
    v = _kernel_inputs(384, length)
    start = _kernel_state(length)
    y, state = _scan(v, state=start, length=length)
    cut = {k: a[:length] if k in ("x", "dt", "b", "c") else a
           for k, a in v.items()}
    want, want_state = _scan(cut, state=start)
    np.testing.assert_allclose(state, want_state, atol=1e-6, rtol=1e-6)
    np.testing.assert_allclose(y[:length], want, atol=1e-6, rtol=1e-6)
    skipped = -(-length // 128) * 128
    np.testing.assert_array_equal(
        y[skipped:], (v["d_skip"][:, None] * v["x"])[skipped:])


def test_scan_kernel_in_two_stretches_is_one_stretch(interpreted):
    """Two stretches, the second from the state the first hands on, are
    the bucket scanned whole, wherever ``length`` falls."""
    v = _kernel_inputs(384, 11)

    def part(lo, hi):
        return {k: a[lo:hi] if k in ("x", "dt", "b", "c") else a
                for k, a in v.items()}

    for length in (384, 300, 100):
        whole, whole_state = _scan(v, state=_kernel_state(), length=length)
        first, handed = _scan(part(0, 256), state=_kernel_state(),
                              length=min(length, 256))
        second, state = _scan(part(256, 384), state=handed,
                              length=max(length - 256, 0))
        np.testing.assert_allclose(state, whole_state, atol=1e-6, rtol=1e-6)
        np.testing.assert_allclose(
            jnp.concatenate([first, second])[:length], whole[:length],
            atol=1e-6, rtol=1e-6)


@pytest.mark.parametrize("form", ["kernel", "xla"])
def test_a_wholly_padded_chunk_is_booked_as_skipped(monkeypatch, form):
    """A prefill's ``counts`` at the served sizes, a 1,024 bucket of 8
    chunks with 300 tokens in it, over 5 state-space layers: the
    kernel's 3 chunks that hold a token ran and 5 were skipped; XLA's
    body runs all 8."""
    monkeypatch.setattr(platform, "pallas_mode",
                        lambda: "interpret" if form == "kernel" else None)
    cfg = {"chunk_size": 128, "mamba_num_heads": 128, "mamba_head_dim": 64,
           "n_groups": 8, "ssm_state_size": 128,
           "layer_kinds": ("M", "E", "M", "*", "M", "M", "M")}
    counts = jax.jit(lambda n: sm._counts(cfg, [], 1024, n))(300)
    ran = 3 if form == "kernel" else 8
    assert [int(c) for c in counts[-3:]] == [
        5 * 300, 5 * ran, 5 * (8 - ran)]
    assert [int(c) for c in sm._counts(cfg, [], 0, 0)[-3:]] == [0, 0, 0]
    model = "ssm_book_" + form
    sm.book(model, np.asarray(counts))
    assert _counter("ssm_prefill_chunks_run_total", model=model) == 5 * ran
    assert _counter("ssm_prefill_chunks_skipped_total", model=model) \
        == 5 * (8 - ran)


@pytest.mark.parametrize("interpret", [False, True], ids=["xla", "kernel"])
def test_pool_update_reads_and_writes_the_named_rows_only(monkeypatch,
                                                          interpret):
    """Three rows of a batch over a pool of nine states: each reads
    ``pool[read[i]]`` and writes ``pool[write[i]]``, the pad row the
    pool's last; every other row of the pool comes back as it went in,
    by XLA's gather and scatter and by the kernel under the interpreter
    (whole tiles: 128 channels a group, a state of 8)."""
    monkeypatch.setattr(platform, "pallas_mode",
                        lambda: "interpret" if interpret else None)
    jax.clear_caches()
    rng = np.random.RandomState(3)
    heads, p, groups, n = 8, 32, 2, 8
    v = _scan_inputs(3, 4, heads, p, groups, n)
    pool = jnp.asarray(rng.randn(9, groups, n, heads * p // groups),
                       jnp.float32)
    read, write = jnp.asarray([4, 0, 7]), jnp.asarray([2, 8, 5])
    calls = str(jax.make_jaxpr(lambda q: state_space.ssm_update(
        v["x"], v["dt"], v["a_rate"], v["b"], v["c"], v["d_skip"], q, read,
        write))(pool)).count("pallas_call")
    assert calls == (1 if interpret else 0)
    y, out = jax.jit(lambda q: state_space.ssm_update(
        v["x"], v["dt"], v["a_rate"], v["b"], v["c"], v["d_skip"], q, read,
        write))(pool)
    want_y, want_state = state_space.ssm_step(
        v["x"], v["dt"], v["a_rate"], v["b"], v["c"], v["d_skip"],
        pool[read])
    np.testing.assert_allclose(y, want_y, atol=1e-5, rtol=1e-5)
    np.testing.assert_allclose(out[write], want_state, atol=1e-5, rtol=1e-5)
    kept = [i for i in range(9) if i not in (2, 8, 5)]
    np.testing.assert_array_equal(out[np.asarray(kept)],
                                  pool[np.asarray(kept)])
    jax.clear_caches()


def test_convolution_with_a_carried_tail_is_the_whole_convolution():
    """``ops/short_conv.py``, the one function of the three families: a
    prompt convolved in two stretches, the second from the first's tail,
    is the prompt convolved whole, and a one-token step from that tail
    is the next row; the tail at ``length`` is the rows before it."""
    rng = np.random.RandomState(5)
    u = jnp.asarray(rng.randn(20, 6), jnp.float32)
    w = jnp.asarray(rng.randn(6, 4), jnp.float32)
    whole, last = short_conv.conv_prefill(u, w)
    first, tail = short_conv.conv_prefill(u[:9], w)
    second, tail2 = short_conv.conv_prefill(u[9:], w, tail=tail)
    np.testing.assert_allclose(jnp.concatenate([first, second]), whole,
                               atol=1e-6)
    np.testing.assert_array_equal(tail2, last)
    np.testing.assert_array_equal(last, u[17:])
    _, at5 = short_conv.conv_prefill(u, w, length=5)
    np.testing.assert_array_equal(at5, u[2:5])
    # a stretch with no token in it hands its tail on
    _, kept = short_conv.conv_prefill(u[9:], w, length=0, tail=tail)
    np.testing.assert_array_equal(kept, tail)
    step, moved = short_conv.conv_step(tail[None], u[9][None], w)
    np.testing.assert_allclose(step[0], whole[9], atol=1e-6)
    np.testing.assert_array_equal(moved[0], u[7:10])
    assert short_conv.tail_shape(3, 10240) == (60, 512)
    assert short_conv.tail_shape(3, 64) == (3, 64)


@pytest.mark.parametrize("length", [3, 16, 30, 48])
def test_a_long_prompt_in_stretches_is_the_prompt_whole(model, monkeypatch,
                                                        length):
    """The Mamba-2 layers of a 48-token bucket in three stretches of 16
    that hand state and tail on (the 16,384 bucket runs as four of
    4096): the same logits, cache rows of the prompt's tokens and state
    as the bucket run whole, wherever ``length`` falls among the
    stretches (a pad position's rows, which no one reads, see the tail
    as of ``length`` and differ)."""
    cfg, params = model
    toks = jnp.asarray(_tokens(48, 21))
    whole = jax.jit(lambda p, t, n: sm.prefill(p, t, n, cfg))(
        params, toks, length)
    monkeypatch.setattr(sm, "SEGMENT", 16)
    assert sm._segment(48, cfg) == 16 and sm._segment(16, cfg) == 16
    cut = jax.jit(lambda p, t, n: sm.prefill(p, t, n, cfg))(
        params, toks, length)
    def read(out):
        logits, k, v, counts, state = out
        return [logits, k[:, :length], v[:, :length], counts, *state]

    for a, b in zip(read(whole), read(cut)):
        np.testing.assert_allclose(a, b, atol=2e-5, rtol=1e-5)


def test_the_served_buckets_run_in_stretches_that_are_whole_iterations():
    cfg = {"chunk_size": 128}
    assert [sm._segment(t, cfg) for t in
            (1024, 2048, 3072, 6144, 8192, 12288, 16384)] == [
        1024, 2048, 3072, 3072, 4096, 4096, 4096]


# ----------------------------------------------------------------------
# (d) the state under a repeat, a dropped step, a failure, two sequences


def test_a_repeated_step_leaves_the_state_as_if_run_once(model, reference):
    """Every decode step dispatched twice (a retry): the second call
    reads the version of the state the first one read, so both return
    the same logits and the sequence goes on as the reference's."""
    be = _backend(model, "ssm_repeat")
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
    be = _backend(model, "ssm_drop")
    toks = _tokens(16, 11)
    want = _reference_logits(reference, model[1], toks)
    be.cache.allocate("s", 16)
    _prefill(be, "s", toks[:4], 8)
    for t in range(4, 16):
        got = _step(be, "s", toks[t], t, run_ahead=t < 15)
        np.testing.assert_allclose(got, want[t], atol=TOL, rtol=0)
    assert _counter("generation_decode_ahead_dropped_total",
                    model="ssm_drop") >= 9   # a greedy id may be the fed one


def test_a_step_that_fails_behind_a_queued_step_is_a_hazard(model):
    from mxnet_tpu import chaos

    be = _backend(model, "ssm_hazard")
    be.cache.allocate("s", 16)
    _prefill(be, "s", _tokens(4), 8)
    with chaos.inject("serving.decode", "raise", match=":fetch", limit=1):
        with pytest.raises(serving.RecurrentStateHazard):
            _step(be, "s", 3, 4, run_ahead=True)
    assert be._ahead is None


def test_two_sequences_keep_their_own_states_in_one_batch(model, reference):
    """Two sequences of different lengths decoded in one batch with a
    pad row: each row reads and writes its own slot's version, the pad
    row the pool's last row; a fifth sequence finds no slot (429)."""
    be = _backend(model, "ssm_two", state_slots=2)
    seqs = {"a": _tokens(14, 1), "b": _tokens(11, 2)}
    starts = {"a": 6, "b": 3}
    want = {s: _reference_logits(reference, model[1], t)
            for s, t in seqs.items()}
    for s, toks in seqs.items():
        be.cache.allocate(s, len(toks))
        _prefill(be, s, toks[:starts[s]], 8)
    with pytest.raises(serving.CacheExhaustedError):
        be.cache.allocate("c", 4)
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
# (e) the expert of two matrices in every form, (f) the shares add up


def _loop_over_experts(x, chosen, gates, w_up, w_down, first):
    """``sum_k gate_k W_down_e relu(W_up_e x)^2`` over the chosen experts
    that are among the ``w_up.shape[0]`` from ``first``, a row and an
    expert at a time."""
    x, chosen, gates = (np.asarray(a, np.float64)
                        for a in (x, chosen, gates))
    up, down = np.asarray(w_up, np.float64), np.asarray(w_down, np.float64)
    out = np.zeros_like(x)
    for t in range(x.shape[0]):
        for j in range(chosen.shape[1]):
            e = int(chosen[t, j]) - first
            if 0 <= e < up.shape[0]:
                h = np.maximum(x[t] @ up[e], 0.0) ** 2
                out[t] += gates[t, j] * (h @ down[e])
    return out


@pytest.mark.parametrize("form", ["every_row", "grouped", "cut"])
def test_two_matrix_expert_in_every_form_is_a_loop_over_experts(form,
                                                                monkeypatch):
    """``dropless_experts(w_gate=None, activation="relu2")`` over 40
    rows choosing 3 of 16 with 6 held: every held expert over every row,
    the grouped products with every pair in one run, and runs of 16
    sorted pairs (an overflow among them) all give the loop's sum; pad
    rows are routed nowhere."""
    rng = np.random.RandomState(2)
    x = jnp.asarray(rng.randn(40, 12), jnp.float32)
    chosen = jnp.asarray(np.stack([rng.permutation(16)[:3]
                                   for _ in range(40)]), jnp.int32)
    gates = jnp.asarray(rng.rand(40, 3), jnp.float32)
    up = jnp.asarray(rng.randn(6, 12, 20) * 0.3, jnp.float32)
    down = jnp.asarray(rng.randn(6, 20, 12) * 0.3, jnp.float32)
    valid = jnp.arange(40) < 33
    if form == "cut":
        monkeypatch.setattr(moe, "grouped_kept_rows", lambda *a: 16)
    n_experts = 6 if form == "grouped" else 16      # 6: every pair kept
    y, counts = jax.jit(lambda *a: moe.dropless_experts(
        *a, None, up, down, (4, 6), valid=valid,
        every_row=form == "every_row", n_experts=n_experts,
        activation="relu2"))(x, chosen, gates)
    want = _loop_over_experts(x[:33], chosen[:33], gates[:33], up, down, 4)
    np.testing.assert_allclose(y[:33], want, atol=2e-5, rtol=1e-5)
    np.testing.assert_array_equal(y[33:], 0)
    held = int(((np.asarray(chosen[:33]) >= 4)
                & (np.asarray(chosen[:33]) < 10)).sum())
    assert [int(c) for c in counts[:2]] == [33 * 3, held]
    assert int(counts[4]) == (-(-held // 16) - 1 if form == "cut" else 0)


def _layer_weights(params, prefix="l1_"):
    return {k[len(prefix):]: v for k, v in params.items()
            if k.startswith(prefix)}


def _routed_part(params, cfg, h, held):
    """The program's expert layer of layer 1 over ``h`` with ``held``
    experts' weights, without what every chip computes alike: the
    shared expert."""
    first, count = held
    cut = dict(params)
    for name in ("up", "down"):
        key = "l1_experts_%s_weight" % name
        cut[key] = params[key][first:first + count]
    # the shared expert counted once: taken out of each share here
    cut["l1_shared_down_weight"] = jnp.zeros_like(
        params["l1_shared_down_weight"])
    return sm._experts(cut, "l1_", h, dict(cfg, held=held))


@pytest.mark.parametrize("every_row", [False, True],
                         ids=["grouped", "every_row"])
@pytest.mark.parametrize("holders", [1, 2, 4])
def test_the_shares_add_up_to_the_uncut_layer(model, reference, holders,
                                              every_row, monkeypatch):
    """The expert layer split over 1, 2 and 4 holders (8, 4 and 2 of the
    tiny 8 each; the cell's deployment is the four): the shares'
    routed parts, each projected back from the latent by the same
    ``W_up``, and the shared expert counted once, add up to the uncut
    reference's layer."""
    cfg, params = model
    monkeypatch.setattr(moe, "few_rows_hit_most", lambda *s: every_row)
    h = jnp.asarray(np.random.RandomState(3).randn(24, 32), jnp.float32)
    per = 8 // holders
    total, local = 0.0, 0
    for s in range(holders):
        out, counts = _routed_part(params, cfg, h, (s * per, per))
        total = total + out
        local += int(counts[1])
        assert int(counts[0]) == 24 * 3
    assert local == 24 * 3              # every pair fell on one holder
    total = total + sm._relu2(h, params["l1_shared_up_weight"],
                              params["l1_shared_down_weight"])
    want = reference._expert_layer(TINY, _layer_weights(params), h,
                                   reference._Math("float32"))
    np.testing.assert_allclose(total, want, atol=5e-5, rtol=1e-5)


def test_sigmoid_router_is_the_reference(model, reference):
    """The choice on score plus bias and the gates renormalised and
    scaled: the program's router call against the reference's, on
    logits with ties."""
    cfg, _ = model
    rng = np.random.RandomState(8)
    logits = jnp.asarray(np.round(rng.randn(30, 8), 1), jnp.float32)
    bias = jnp.asarray(0.1 * rng.randn(8), jnp.float32)
    chosen, gates = moe.route_group_limited(
        logits, bias, top_k=3, n_group=1, topk_group=1, scale=2.5,
        normalize=True)
    want_chosen, want_gates = reference.route(TINY, logits, bias)
    np.testing.assert_array_equal(np.sort(chosen, -1),
                                  np.sort(want_chosen, -1))
    np.testing.assert_allclose(np.sort(gates, -1), np.sort(want_gates, -1),
                               rtol=1e-6)
    np.testing.assert_allclose(gates.sum(-1), 2.5, rtol=1e-5)


# ----------------------------------------------------------------------
# (g) the comparison sees the new mechanism


@pytest.mark.parametrize("fault", ["sound", "zeroed", "stale"])
def test_a_lost_state_fails_the_tiny_limits(model, reference, fault):
    """The control that the cell's ``correct`` sees the state: zeroed at
    the hand-over from prefill to decode, or left one step old, it moves
    the served logits past the limit the tiny cell runs under (1e-3);
    left alone they are within it.  The reference's own control
    (``lost_at``) reads what the zeroed program reads."""
    be = _backend(model, "ssm_fault_" + fault)
    toks = _tokens(14, 13)
    want = _reference_logits(reference, model[1], toks)
    be.cache.allocate("s", 14)
    _prefill(be, "s", toks[:8], 8)
    worst, got_all = 0.0, []
    for t in range(8, 14):
        pools = be.cache.state_pools
        if fault == "zeroed" and t == 8:
            be.cache.swap_state(tuple(jnp.zeros_like(p) for p in pools))
        before = tuple(jnp.array(p) for p in pools)     # the step donates
        got = _step(be, "s", toks[t], t)
        got_all.append(got)
        if fault == "stale" and t == 9:
            be.cache.swap_state(before)     # the step's write is lost
        worst = max(worst, float(np.abs(got - want[t]).max()))
    assert (worst > 1e-3) == (fault != "sound"), worst
    if fault == "zeroed":
        lost = np.asarray(jax.jit(lambda p, t: reference.logits(
            TINY, p, t, "float32", 8))(model[1], toks[None]))[0]
        np.testing.assert_allclose(np.stack(got_all), lost[8:], atol=TOL,
                                   rtol=0)
        np.testing.assert_array_equal(lost[:8], want[:8])


def test_unbuilt_variants_are_refused():
    for key, value in (("use_conv_bias", False), ("mlp_bias", True),
                       ("mlp_hidden_act", "silu"), ("n_shared_experts", 2)):
        with pytest.raises(ValueError, match="not built"):
            sm.lm_config(dict(TINY, **{key: value}), 64)
    with pytest.raises(ValueError, match="hybrid_override_pattern"):
        sm.lm_config(dict(TINY, num_hidden_layers=11), 64)
    with pytest.raises(ValueError, match="hybrid_override_pattern"):
        sm.lm_config(dict(TINY, hybrid_override_pattern="ME-MEME"), 64)
