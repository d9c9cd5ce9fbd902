"""The Pallas kernels behind the hot paths and the one rule that picks
them (``ops/platform.py``, ``ops/fused/parity.py``; PR 28 took the
variant tier of PR 19 out).

- **Parity is falsifiable**: every registered kernel is green on its
  whole grid, and a deliberately broken kernel registered by the test IS
  caught.
- **The platform test is one function** that reads no environment
  variable: ``None`` off the chip, ``"chip"`` where JAX reports a TPU.
- **The rule is in the function**: off the chip a fit and a prefill +
  decode run their reference bodies; with the platform test patched to
  the interpreter the same calls take their kernels and agree with the
  reference bodies within each kernel's class.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh

import mxnet_tpu as mx
from mxnet_tpu.models import transformer as tfm
from mxnet_tpu.ops import attention as att
from mxnet_tpu.ops import paged_attention as paged
from mxnet_tpu.ops import platform
from mxnet_tpu.ops.fused import parity as fpar
from mxnet_tpu.parallel import trainer as ptr

KERNELS = sorted(fpar.parity_registrations())


@pytest.fixture
def interpreted(monkeypatch):
    """Every rule picks its kernel and runs it under the interpreter."""
    monkeypatch.setattr(platform, "pallas_mode", lambda: "interpret")
    jax.clear_caches()
    yield
    jax.clear_caches()


def _pallas_calls(fn, *args):
    return str(jax.make_jaxpr(fn)(*args)).count("pallas_call")


# ------------------------------------------------------------- parity

def test_every_hot_path_kernel_is_registered():
    assert KERNELS == ["flash_prefill_attention", "gated_delta_decode",
                       "latent_decode_attention", "paged_decode_attention",
                       "paged_decode_gqa_attention", "sgd_mom_tree",
                       "ssm_decode", "ssm_prefill"]
    regs = fpar.parity_registrations()
    # the tree step is plain jax on every backend; the rest are Pallas
    assert [k for k in KERNELS if not regs[k].pallas] == ["sgd_mom_tree"]
    assert regs["sgd_mom_tree"].parity == "bitwise"


@pytest.mark.parametrize("kernel", KERNELS)
def test_parity_full_grid_green(kernel, monkeypatch):
    reg = fpar.parity_registrations()[kernel]
    monkeypatch.setattr(fpar, "_PARITY", {kernel: reg})
    rows = fpar.run_parity(quick=False)
    assert len(rows) == len(reg.grid)
    bad = [r for r in rows if not r["ok"]]
    assert not bad, bad


def test_parity_quick_grid_trims_to_two_cases():
    rows = fpar.run_parity(quick=True)
    assert {r["kernel"] for r in rows} == set(KERNELS)
    assert all(sum(r["kernel"] == k for r in rows) == 2 for k in KERNELS)
    assert all(r["ok"] for r in rows), [r for r in rows if not r["ok"]]


def test_parity_catches_broken_kernel(monkeypatch):
    """The falsifiability gate: a kernel that is wrong by 1e-3 must
    fail its bitwise parity row — if this test fails, the harness is
    decoration."""
    def broken(x):
        return x * 1.0 + 1e-3

    def reference(x):
        return x * 1.0

    monkeypatch.setattr(fpar, "_PARITY", {})
    fpar.register_parity(
        "test_broken",
        lambda case: (reference, broken, (jnp.arange(4.0) + case,)),
        grid=(0.0, 1.0), pallas=False)
    rows = fpar.run_parity(quick=True)
    assert len(rows) == 2 and all(not r["ok"] for r in rows)
    assert "bits differ" in rows[0]["detail"]


def test_parity_cli_prints_one_row_a_case_and_exits_zero(capsys):
    assert fpar.main(["--quick"]) == 0
    out = capsys.readouterr().out.splitlines()
    assert out[-1] == "parity: %d cases, 0 failed, %d kernels" % (
        2 * len(KERNELS), len(KERNELS))
    assert sum(line.startswith("ok ") for line in out) == 2 * len(KERNELS)


@pytest.mark.parametrize("fault", ["empty-grid", "unknown-class"])
def test_register_parity_refuses_a_hollow_registration(fault, monkeypatch):
    monkeypatch.setattr(fpar, "_PARITY", {})
    kwargs = {"grid": ()} if fault == "empty-grid" else \
        {"grid": (0,), "parity": "close-enough"}
    with pytest.raises(ValueError):
        fpar.register_parity("test_hollow", lambda case: None, **kwargs)
    assert fpar.parity_registrations() == {}


# --------------------------------------------------- the platform test

def test_pallas_mode_off_the_chip_is_none():
    if jax.default_backend() == "tpu":
        pytest.skip("a host-side check")
    assert platform.pallas_mode() is None


def test_pallas_mode_follows_the_backend_and_caches_nothing(monkeypatch):
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert platform.pallas_mode() == "chip"
    monkeypatch.setattr(jax, "default_backend", lambda: "cpu")
    assert platform.pallas_mode() is None
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert platform.pallas_mode() == "chip"


def test_pallas_mode_reads_no_environment():
    import inspect

    source = inspect.getsource(platform)
    assert "os.environ" not in source and "getenv" not in source


# ------------------------------------------- the rule, in the function

def _qkv(t_q, t_k, seed=0):
    rng = np.random.RandomState(seed)
    return (jnp.asarray(rng.randn(1, 2, t_q, 16), jnp.float32),
            jnp.asarray(rng.randn(1, 2, t_k, 16), jnp.float32),
            jnp.asarray(rng.randn(1, 2, t_k, 16), jnp.float32))


@pytest.mark.parametrize("shape", ["prefill", "continuation"])
def test_prefill_attention_rule(shape, interpreted):
    """Where Pallas runs, a self-attention prefill is the flash
    kernel's; a continuation (k longer than q) keeps the stable body,
    whose causal mask is offset, bit for bit."""
    q, k, v = _qkv(24, 24) if shape == "prefill" else _qkv(8, 24)
    ref = att._stable_causal_attention(q, k, v, 0.25)
    got = att.stable_causal_attention(q, k, v)
    assert got.dtype == jnp.float32
    if shape == "prefill":
        assert _pallas_calls(att.stable_causal_attention, q, k, v) == 1
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   rtol=2e-5, atol=2e-5)
    else:
        assert _pallas_calls(att.stable_causal_attention, q, k, v) == 0
        np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))


def test_prefill_attention_off_the_chip_is_the_stable_body():
    q, k, v = _qkv(24, 24)
    assert _pallas_calls(att.stable_causal_attention, q, k, v) == 0
    np.testing.assert_array_equal(
        np.asarray(att.stable_causal_attention(q, k, v)),
        np.asarray(att._stable_causal_attention(q, k, v, 0.25)))


def _fit_state(steps=3):
    """A small bare-momentum SGD fit (the shape that engages the
    whole-tree optimizer step); returns (weight, momentum) numpy
    arrays."""
    mesh = Mesh(np.array(jax.devices()[:1]), ("data",))
    fc = mx.sym.FullyConnected(mx.sym.Variable("data"), num_hidden=1,
                               no_bias=True, name="fc")
    sym = mx.sym.MakeLoss(fc, name="loss")
    tr = ptr.ShardedTrainer(sym, mesh, data_shapes={"data": (4, 6)},
                            learning_rate=0.05, momentum=0.9)
    params, moms, aux = tr.init(seed=0)
    data = np.random.RandomState(0).randn(4, 6).astype(np.float32)
    batch = tr.place_batch({"data": data})
    step = tr.step_fn()
    for i in range(steps):
        _, params, moms, aux = step(params, moms, aux, batch,
                                    jax.random.PRNGKey(i))
    return np.asarray(params["fc_weight"]), np.asarray(moms["fc_weight"])


def test_fit_with_the_tree_step_equals_the_reference_spelling(monkeypatch):
    """The trainer calls ``fused_sgd_mom_tree`` directly; a fit through
    it ends on the bits of one through the per-parameter reference
    spelling put in its place."""
    calls = []

    def reference(*args):
        calls.append(1)
        return ptr.sgd_mom_tree_stock(*args)

    w, m = _fit_state()
    assert not calls
    monkeypatch.setattr(ptr, "fused_sgd_mom_tree", reference)
    w_ref, m_ref = _fit_state()
    assert calls                      # the step was traced through it
    np.testing.assert_array_equal(w, w_ref)
    np.testing.assert_array_equal(m, m_ref)


# 2 heads of 64 and blocks of 8: a cached row is one lane tile wide and
# a float32 page whole tiles, so the decode's shape rule lets the kernel in
_CFG = dict(num_classes=32, seq_len=32, num_embed=128, num_heads=2,
            num_layers=2)


def _generate_logits():
    """LM prefill + two paged decode steps through the public entry
    points; returns the concatenated logits."""
    cfg = tfm.lm_config(**_CFG)
    params = tfm.init_lm_params(cfg, seed=0)
    toks = (np.arange(6, dtype=np.int32) % 32)[None, :]
    logits, k, v = tfm.lm_prefill(params, toks, cfg)
    out = [np.asarray(logits)]
    # a 1-sequence paged cache: one block per 8 tokens, identity table
    blk, max_blocks = 8, 4
    L = cfg["num_layers"]
    h, d = cfg["num_heads"], cfg["num_embed"] // cfg["num_heads"]
    k_pages = np.zeros((L, max_blocks, blk, h, d), np.float32)
    v_pages = np.zeros((L, max_blocks, blk, h, d), np.float32)
    t = toks.shape[1]
    k_np, v_np = np.asarray(k), np.asarray(v)
    for pos in range(t):
        k_pages[:, pos // blk, pos % blk] = k_np[:, 0, pos]
        v_pages[:, pos // blk, pos % blk] = v_np[:, 0, pos]
    bt = np.arange(max_blocks, dtype=np.int32)[None, :]
    for step_i in range(2):
        pos = t + step_i
        tok = np.asarray([(7 * step_i + 3) % 32], np.int32)
        lg, ks, vs = tfm.lm_decode_step(
            params, tok, np.asarray([pos], np.int32),
            jnp.asarray(k_pages), jnp.asarray(v_pages), bt,
            np.asarray([pos + 1], np.int32), cfg)
        out.append(np.asarray(lg))
        k_pages[:, pos // blk, pos % blk] = np.asarray(ks)[:, 0]
        v_pages[:, pos // blk, pos % blk] = np.asarray(vs)[:, 0]
    return np.concatenate([o.reshape(-1) for o in out])


def test_generate_with_the_kernels_equals_the_reference_bodies(monkeypatch):
    """A prefill and two decode steps with the kernels (the platform
    test patched, ``interpret=True``: the flash prefill and the
    block-table walk, both class ``tolerance``) against the same calls
    with the reference bodies."""
    reference = _generate_logits()
    monkeypatch.setattr(platform, "pallas_mode", lambda: "interpret")
    jax.clear_caches()
    try:
        cfg = tfm.lm_config(**_CFG)
        params = jax.tree_util.tree_map(
            jnp.asarray, tfm.init_lm_params(cfg, seed=0))
        toks = jnp.zeros((1, 6), jnp.int32)
        # the layers share one trace of the jitted kernel and call it
        # once each
        text = str(jax.make_jaxpr(
            lambda p, t: tfm.lm_prefill(p, t, cfg)[0])(params, toks))
        assert text.count("pallas_call") == 1
        assert text.count("name=_flash_fwd_pallas") == cfg["num_layers"]
        kernels = _generate_logits()
    finally:
        jax.clear_caches()
    assert np.abs(reference).max() > 1e-2
    np.testing.assert_allclose(kernels, reference, rtol=2e-4, atol=2e-5)


def _decode_args(width):
    """One row with 12 cached tokens over blocks of 8, 2 heads."""
    rng = np.random.RandomState(1)
    pool = jnp.asarray(rng.randn(5, 8, width), jnp.float32)
    tables = jnp.asarray([[1, 2, 0, 0]], jnp.int32)
    return rng, pool, tables, jnp.asarray([13], jnp.int32)


def _call_prefill():
    return att.stable_causal_attention(*_qkv(24, 24))


def _call_kv_decode():
    rng, pool, tables, lens = _decode_args(128)
    q, k_step, v_step = (jnp.asarray(rng.randn(1, 2, 64), jnp.float32)
                         for _ in range(3))
    pages = pool.reshape(5, 8, 2, 64)
    return paged.paged_decode_attention(q, k_step, v_step, pages, pages,
                                        tables, lens)


def _call_latent_decode():
    rng, pool, tables, lens = _decode_args(256)
    q = jnp.asarray(rng.randn(1, 2, 256), jnp.float32)
    row = jnp.asarray(rng.randn(1, 256), jnp.float32)
    return paged.latent_paged_decode_attention(q, row, pool, tables, lens,
                                               0.1, 128)


@pytest.mark.parametrize("module,kernel,call", [
    (att, "_flash_fwd_pallas", _call_prefill),
    (paged, "_walk_pages", _call_kv_decode),
    (paged, "_walk_pages", _call_latent_decode),
], ids=["prefill", "kv-decode", "latent-decode"])
def test_a_kernel_body_that_raises_is_not_caught(interpreted, monkeypatch,
                                                 module, kernel, call):
    """No fallback book: what a kernel raises while it is traced reaches
    the caller of the public function (which, sound, returns finite
    numbers through that kernel)."""
    assert np.isfinite(np.asarray(call())).all()
    jax.clear_caches()

    def broken(*args, **kwargs):
        raise RuntimeError("kernel exploded")

    monkeypatch.setattr(module, kernel, broken)
    with pytest.raises(RuntimeError, match="kernel exploded"):
        call()
