"""Generation lane (serving/generation.py + ops/kv_cache.py): the
round-14 acceptance gates.

- **Parity**: incremental decode through the paged cache equals the
  full-sequence forward (same greedy tokens, logits within 8 float32
  spacings of the row's largest) — the KV cache is an optimization,
  never an approximation.
- **Zero steady-state recompiles**: after :meth:`warmup`, generating at
  any admitted prompt length / batch size compiles nothing.
- **Iteration-level admission**: a request submitted mid-generation
  joins the NEXT decode step (Orca), witnessed by the step-row stats.
- **Paged-cache lifecycle**: alloc/free/exhaustion → typed 429 through
  the stock admission accounting.
- **Chaos**: a mid-generation ``serving.decode`` fault retries without
  corrupting any other sequence's blocks (bitwise vs a no-chaos run).
- **Streaming**: chunked-HTTP round-trip on ``/v1/generate``; an early
  client disconnect cancels the request and frees its blocks.
"""

import http.client
import json
import socket
import time

import numpy as np
import pytest

from mxnet_tpu import chaos, serving
from mxnet_tpu.base import MXNetError
from mxnet_tpu.contrib.quantization import quantize_weight_int8
from mxnet_tpu.models import transformer as tfm
from mxnet_tpu.ops import kv_cache
from mxnet_tpu.ops.kv_cache import (CacheExhaustedError, CachePoolLostError,
                                    PagedKVCache)

VOCAB, SEQ_LEN, EMBED, HEADS, LAYERS = 64, 48, 16, 2, 2


@pytest.fixture(scope="module")
def lm():
    cfg = tfm.lm_config(num_classes=VOCAB, seq_len=SEQ_LEN,
                        num_embed=EMBED, num_heads=HEADS,
                        num_layers=LAYERS)
    return cfg, tfm.init_lm_params(cfg, seed=0)


def _backend(lm, **kw):
    cfg, params = lm
    kw.setdefault("block_size", 4)
    kw.setdefault("num_blocks", 64)
    return serving.LMBackend(params, cfg, **kw)


def _scheduler(lm, name="lm", **kw):
    sched = serving.GenerationScheduler()
    be = _backend(lm, **kw)
    sched.register(name, be, decode_buckets=[1, 2, 4],
                   prefill_buckets=[8, 16])
    sched.warmup(name)
    return sched, be


# ---------------------------------------------------------------- parity

def _row_ulps(got, ref):
    """Largest difference between two logit rows, in float32 spacings
    at the row's largest logit."""
    return float(np.abs(got - ref).max() / np.spacing(np.abs(ref).max()))


def test_decode_equals_full_forward_to_the_last_bits(lm):
    """The parity gate: token t's logits from the incremental decode
    path (paged cache, padded block tables, padded decode batch) equal
    the full-sequence forward at row t — the same greedy token at every
    position and every logit within 8 float32 spacings of the row's
    largest.  Measured on jaxlib 0.9: 3 on this prompt, at most 4 over
    twenty prompts of 1-8 tokens and 9 steps each, while the closest
    runner-up logit stood over 1000 spacings away.  It was
    ``np.array_equal`` until XLA:CPU stopped summing a dot the same way
    for every shape: the p.v contraction groups its adds by the key
    count modulo 4 and a one-row matmul takes another kernel than a
    T-row one, so a 5-token forward, an 8-padded prefill and a decode
    step agree to the last few bits and no further.  A dropped
    precision (bf16 anywhere) or a wrong cache page is thousands of
    spacings: the gate still catches what it is for."""
    cfg, params = lm
    rng = np.random.RandomState(3)
    prompt = rng.randint(0, VOCAB, size=5).astype(np.int32)
    steps = 9

    # reference: re-run the full forward at every length
    toks = list(prompt)
    ref_logits = []
    for _ in range(steps):
        logits, _, _ = tfm.lm_prefill(
            params, np.asarray(toks, np.int32)[None], cfg)
        row = np.asarray(logits)[0, len(toks) - 1]
        ref_logits.append(row)
        toks.append(int(np.argmax(row)))

    # incremental: one prefill + paged decode steps
    be = _backend(lm)
    pref_logits, k, v, _ = be.prefill(
        np.pad(prompt, (0, 8 - prompt.size)), prompt.size)
    assert _row_ulps(pref_logits, ref_logits[0]) <= 8, \
        "prefill logits differ from full forward"
    be.cache.allocate("s", prompt.size + steps)
    be.cache.write_prefill("s", k, v, prompt.size)
    generated = [int(np.argmax(pref_logits))]
    length = int(prompt.size)
    for t in range(1, steps):
        tables = be.cache.block_table("s", be.max_blocks_per_seq)[None]
        logits, ks, vs, _ = be.decode(
            np.array(generated[-1:], np.int32),
            np.array([length], np.int32),
            tables, np.array([length + 1], np.int32))
        assert _row_ulps(logits[0], ref_logits[t]) <= 8, \
            "decode step %d logits differ from full forward" % t
        length += 1        # the step wrote its own K/V: nothing to do
        generated.append(int(np.argmax(logits[0])))
    assert generated == toks[len(prompt):]


def test_generate_matches_full_forward_argmax(lm):
    """End-to-end scheduler path reproduces the naive re-prefill chain."""
    cfg, params = lm
    sched, _ = _scheduler(lm)
    prompt = np.array([3, 9, 1, 7], np.int32)
    out = sched.generate("lm", prompt, max_new_tokens=8)
    toks = list(prompt)
    ref = []
    for _ in range(8):
        logits, _, _ = tfm.lm_prefill(
            params, np.asarray(toks, np.int32)[None], cfg)
        nxt = int(np.argmax(np.asarray(logits)[0, len(toks) - 1]))
        ref.append(nxt)
        toks.append(nxt)
    assert out == ref
    sched.close()


def test_zero_steady_state_recompiles(lm):
    """After warmup, generation at every admitted shape compiles
    nothing — generation_compiles_total stays flat."""
    sched, _ = _scheduler(lm)
    compiles = sched._fam["compiles"].labels("lm")
    warm = compiles.value
    assert warm > 0, "warmup should have compiled the bucket ladder"
    for n, length in ((1, 3), (3, 6), (2, 12)):
        reqs = [sched.submit("lm",
                             np.arange(1, 1 + length).astype(np.int32),
                             max_new_tokens=5) for _ in range(n)]
        for r in reqs:
            assert len(r.result(timeout=30)) == 5
    assert compiles.value == warm, "steady-state generation recompiled"
    sched.close()


# ------------------------------------------------------------ int8 head

def test_int8_quantization_grid():
    w = np.linspace(-2.0, 3.0, 24, dtype=np.float32).reshape(6, 4)
    wq, scale = quantize_weight_int8(w)
    assert wq.dtype == np.int8 and wq.max() <= 127 and wq.min() >= -127
    assert np.abs(wq.astype(np.float32) * scale - w).max() <= scale / 2 + 1e-6


def test_int8_head_decode(lm):
    """Opt-in int8 vocab head: decode still streams tokens, and its
    logits stay within one quantization step of the fp32 head."""
    cfg, params = lm
    sched, be = _scheduler(lm, int8_head=True)
    assert "pred_weight_q" in be.params and be.describe()["int8_head"]
    prompt = np.array([3, 9, 1, 7], np.int32)
    out = sched.generate("lm", prompt, max_new_tokens=6)
    assert len(out) == 6
    # bound the head error against the fp32 reference decode
    fp = _backend(lm)
    logits, k, v, _ = fp.prefill(np.pad(prompt, (0, 8 - 4)), 4)
    fp.cache.allocate("s", 10)
    fp.cache.write_prefill("s", k, v, 4)
    tables = fp.cache.block_table("s", fp.max_blocks_per_seq)[None]
    ref, _, _, _ = fp.decode(np.array([out[0]], np.int32),
                             np.array([4], np.int32), tables,
                             np.array([5], np.int32))
    be.cache.allocate("s", 10)     # int8 backend: replay the same step
    be.cache.write_prefill("s", k, v, 4)
    tables8 = be.cache.block_table("s", be.max_blocks_per_seq)[None]
    got, _, _, _ = be.decode(np.array([out[0]], np.int32),
                             np.array([4], np.int32), tables8,
                             np.array([5], np.int32))
    scale = float(be.params["pred_scale"])
    # error budget: weight rounding (scale/2) times the activation l1
    assert np.abs(got[0] - ref[0]).max() < scale * EMBED
    sched.close()


# ------------------------------------------------------- resident state

def _pools(cache):
    return np.array(cache.k_pages), np.array(cache.v_pages)


def test_decode_pad_rows_write_nowhere(lm):
    """A decode step at bucket 4 with ONE live row, while another
    sequence holds block 0 (where a pad row's all-zero table and
    position 0 point): after the step's write, made inside the call,
    every slot of both pools but the live row's own is bit-identical,
    and writing the step again changes nothing."""
    be = _backend(lm)
    rng = np.random.RandomState(5)
    be.cache.allocate("other", 4)
    assert be.cache.block_table("other", 1)[0] == 0
    _, k, v, _ = be.prefill(rng.randint(0, VOCAB, 8).astype(np.int32), 4)
    be.cache.write_prefill("other", k, v, 4)
    be.cache.allocate("live", 8)
    _, k, v, _ = be.prefill(rng.randint(0, VOCAB, 8).astype(np.int32), 5)
    be.cache.write_prefill("live", k, v, 5)
    k0, v0 = _pools(be.cache)

    tables = np.zeros((4, be.max_blocks_per_seq), np.int32)
    tables[0] = be.cache.block_table("live", be.max_blocks_per_seq)
    positions = np.array([5, 0, 0, 0], np.int32)
    _, ks, vs, _ = be.decode(np.array([7, 0, 0, 0], np.int32), positions,
                             tables, np.array([6, 1, 1, 1], np.int32))
    k1, v1 = _pools(be.cache)       # the call wrote the step itself
    be.cache.write_tokens(tables, positions, ks, vs)
    for once, again in zip((k1, v1), _pools(be.cache)):
        assert np.array_equal(once, again), "a second write moved the pool"
    blk, off = tables[0][5 // 4], 5 % 4
    for before, after, step in ((k0, k1, ks), (v0, v1, vs)):
        assert np.array_equal(after[:, blk, off], np.asarray(step)[:, 0])
        changed = np.argwhere((before != after).any(axis=(0, 3)))
        assert changed.tolist() == [[blk, off]], \
            "a pad row's K/V reached the pool"
    assert be.cache.length("live") == 6 and be.cache.length("other") == 4


def test_prefill_writes_length_positions_and_no_more(lm):
    """A prefill at bucket 8 with ``length`` 5 writes five positions of
    the sequence's blocks and no sixth."""
    be = _backend(lm)
    be.cache.allocate("s", 8)                   # two blocks of 4
    _, k, v, _ = be.prefill(np.arange(1, 9, dtype=np.int32), 5)
    assert k.shape == (LAYERS, 8, EMBED)   # rows of heads * dim
    be.cache.write_prefill("s", k, v, 5)
    table = be.cache.block_table("s", 2)
    for pool, src in zip(_pools(be.cache), (k, v)):
        rows = pool[:, table].reshape(LAYERS, 8, EMBED)
        assert np.array_equal(rows[:, :5], np.asarray(src)[:, :5])
        assert not rows[:, 5:].any(), "a pad position was written"
        others = np.delete(pool, table, axis=1)
        assert not others.any(), "another sequence's block was written"
    assert be.cache.length("s") == 5
    with pytest.raises(MXNetError):             # beyond the allocation
        be.cache.write_prefill("s", np.zeros((LAYERS, 16, EMBED)),
                               np.zeros((LAYERS, 16, EMBED)), 9)
    with pytest.raises(MXNetError):             # beyond the table
        be.cache.write_tokens(table[None], [8], k[:, :1], v[:, :1])
    be.cache.free("s")
    with pytest.raises(MXNetError):             # into a freed block
        be.cache.write_tokens(table[None], [5], k[:, :1], v[:, :1])


def test_everything_a_call_reads_is_resident(lm):
    """After a scheduler run every leaf of ``backend.params`` and both
    pools are device arrays, and the benchmark's own expression for the
    host bytes a decode call stages gives 0."""
    import jax

    for int8 in (False, True):
        sched, be = _scheduler(lm, int8_head=int8)
        assert len(sched.generate("lm", [3, 9, 1, 7],
                                  max_new_tokens=6)) == 6
        sched.close()
        assert isinstance(be.params, dict) and "pred_weight" in be.params
        for name, leaf in be.params.items():
            assert isinstance(leaf, jax.Array), name
        assert isinstance(be.cache.k_pages, jax.Array)
        assert isinstance(be.cache.v_pages, jax.Array)
        staged = sum(a.nbytes for a in list(be.params.values())
                     + [be.cache.k_pages, be.cache.v_pages]
                     if isinstance(a, np.ndarray))
        assert staged == 0


def test_pool_writes_alias_the_pool(lm):
    """Donation took: the compiled write program (the one program that
    has the pool among its outputs, at a prefill and at a decode shape)
    aliases both pools to its outputs, so the pool never exists twice;
    the prefill and decode programs have no pool-shaped output at all.
    Checked on the lowered programs, not by timing."""
    import jax

    be = _backend(lm)
    cache = be.cache
    pool = jax.ShapeDtypeStruct(cache.k_pages.shape, cache.k_pages.dtype)
    for rows in (8, 4):            # a prefill bucket, a decode bucket
        kv = jax.ShapeDtypeStruct((LAYERS, rows, EMBED), np.float32)
        idx = jax.ShapeDtypeStruct((rows,), np.int32)
        text = kv_cache._write_pages.lower(
            pool, pool, kv, kv, idx, idx).compile().as_text()
        header = next(l for l in text.splitlines()
                      if l.startswith("HloModule"))
        assert "input_output_alias={ {0}: (0, {}, may-alias), " \
               "{1}: (1, {}, may-alias) }" in header, header
    # the dispatches themselves never hold a second pool: donated
    # buffers are gone after a write, and the pool is re-bound
    old_k, old_v = cache.k_pages, cache.v_pages
    cache.allocate("s", 8)
    _, k, v, _ = be.prefill(np.arange(1, 9, dtype=np.int32), 8)
    for out in jax.tree_util.tree_leaves((k, v)):
        assert out.shape != cache.k_pages.shape
    cache.write_prefill("s", k, v, 8)
    assert old_k.is_deleted() and old_v.is_deleted()
    assert not cache.k_pages.is_deleted()


def test_transfer_counters_read_logits_out_and_kilobytes_in(lm):
    """``generation_*_bytes_total``: a decode call at bucket ``B`` copies
    ``B x V x 4`` bytes back and hands over only ids, positions,
    lengths and slot indices, and the block table when it is not the
    array the call before was handed; a numpy array slipping back among
    the weights shows at once."""
    from mxnet_tpu.observability import metrics as om

    be = _backend(lm, model="bytes")
    h2d = om.REGISTRY.get("generation_host_to_device_bytes_total")
    d2h = om.REGISTRY.get("generation_device_to_host_bytes_total")
    be.cache.allocate("s", 16)
    _, k, v, _ = be.prefill(np.arange(1, 9, dtype=np.int32), 6)
    assert d2h.labels("bytes", "prefill").value == VOCAB * 4
    assert h2d.labels("bytes", "prefill").value == 8 * 4 + 4
    be.cache.write_prefill("s", k, v, 6)
    bucket = 4
    tables = np.zeros((bucket, be.max_blocks_per_seq), np.int32)
    tables[0] = be.cache.block_table("s", be.max_blocks_per_seq)
    call = (np.zeros(bucket, np.int32), np.array([6, 0, 0, 0], np.int32),
            tables, np.array([7, 1, 1, 1], np.int32))
    be.decode(*call)
    # the logits and their greedy ids out; ids, positions, lengths,
    # tables and the write's two slot vectors in
    assert d2h.labels("bytes", "decode").value == bucket * (VOCAB + 1) * 4
    per_call = bucket * 4 * (5 + be.max_blocks_per_seq)
    assert h2d.labels("bytes", "decode").value == per_call
    # the same table object again is the same table: it stays where it
    # is and is not booked; a copy of it is another table, and crosses
    be.params["pred_bias"] = np.asarray(be.params["pred_bias"])
    be.decode(*call)
    assert h2d.labels("bytes", "decode").value \
        == 2 * per_call - tables.nbytes + VOCAB * 4
    be.decode(call[0], call[1], tables.copy(), call[3])
    assert h2d.labels("bytes", "decode").value \
        == 3 * per_call - tables.nbytes + 2 * VOCAB * 4


def test_lost_pool_fails_live_sequences_and_serves_again(lm, monkeypatch):
    """A pool write that fails AFTER its buffers were donated is not
    retried on the consumed buffers: the cache rebuilds a zeroed pool,
    the lane fails its live sequences, frees their blocks and goes on
    serving."""
    sched, be = _scheduler(lm)
    clean = sched.generate("lm", [1, 2, 3], max_new_tokens=6)
    real = kv_cache._write_pages
    calls = {"n": 0}

    def consumed(k_pages, v_pages, *rest):
        calls["n"] += 1
        if calls["n"] == 3:           # a decode write, mid-generation
            k_pages.delete()
            v_pages.delete()
            raise RuntimeError("device fault after donation")
        return real(k_pages, v_pages, *rest)

    monkeypatch.setattr(kv_cache, "_write_pages", consumed)
    req = sched.submit("lm", np.array([1, 2, 3], np.int32),
                       max_new_tokens=6)
    with pytest.raises(CachePoolLostError):
        req.result(timeout=30)
    deadline = time.monotonic() + 10
    while be.cache.stats()["used"] and time.monotonic() < deadline:
        time.sleep(0.01)
    assert be.cache.stats()["used"] == 0
    assert not be.cache.k_pages.is_deleted()
    assert not np.array(be.cache.k_pages).any()
    assert sched.generate("lm", [1, 2, 3], max_new_tokens=6) == clean
    sched.close()


# ------------------------------------------------------- cache lifecycle

def test_paged_cache_alloc_free_lifecycle():
    cache = PagedKVCache(num_layers=1, num_heads=2, head_dim=4,
                         block_size=4, num_blocks=8)
    assert cache.stats()["free"] == 8
    cache.allocate("a", 6)            # 2 blocks
    cache.allocate("b", 9)            # 3 blocks
    assert cache.stats()["used"] == 5
    ta = cache.block_table("a", 4)
    assert ta.shape == (4,) and ta.dtype == np.int32
    # idempotent grow: re-allocating within the reservation adds nothing
    cache.allocate("a", 6)
    assert cache.stats()["used"] == 5
    cache.allocate("a", 12)           # grows by 1 block
    assert cache.stats()["used"] == 6
    freed = cache.free("a")
    assert len(freed) == 3 and cache.free("a") == []
    cache.free("b")
    assert cache.stats()["used"] == 0 and cache.stats()["free"] == 8
    assert cache.free("unknown") == []


def test_cache_exhaustion_is_typed_429():
    cache = PagedKVCache(num_layers=1, num_heads=2, head_dim=4,
                         block_size=4, num_blocks=4)
    cache.allocate("a", 12)           # 3 of 4 blocks
    with pytest.raises(CacheExhaustedError) as ei:
        cache.allocate("b", 8)        # needs 2, only 1 left
    assert ei.value.http_status == 429
    # atomic: the failed allocate took nothing
    assert cache.stats()["used"] == 3
    assert "b" not in cache.sequences()


def test_exhaustion_sheds_through_admission(lm):
    """A prompt the cache cannot hold fails its request with the typed
    429 and books reason=cache_exhausted — existing sequences and later
    requests are untouched."""
    # 6 blocks of 4 = 24 token slots; each request reserves
    # prompt + max_new_tokens up front
    sched, be = _scheduler(lm, num_blocks=6)
    rejected = sched.admission._rejected.labels("lm", "cache_exhausted", "default")
    before = rejected.value
    # slow decode keeps r1's 4 blocks held while r2 tries to allocate
    with chaos.inject("serving.decode", "delay", prob=1.0, seed=1,
                      delay=0.05):
        r1 = sched.submit("lm", np.arange(1, 9, dtype=np.int32),
                          max_new_tokens=8)   # 16 slots -> 4 blocks
        r2 = sched.submit("lm", np.arange(1, 9, dtype=np.int32),
                          max_new_tokens=8)   # 4 more blocks: exhausted
        with pytest.raises(CacheExhaustedError):
            r2.result(timeout=30)
        assert len(r1.result(timeout=30)) == 8
    assert rejected.value == before + 1
    # blocks were released; the lane still serves
    assert sched.generate("lm", [5, 6], max_new_tokens=4)
    assert be.cache.stats()["used"] == 0
    sched.close()


def test_frontend_cache_exhaustion_429_round_trip(lm):
    """A prefill-time ``CacheExhaustedError`` maps to a REAL 429 on
    ``/v1/generate`` — not an error tail riding a committed 200 — and
    the reply carries ``Retry-After`` plus the pool's occupancy hints
    in the JSON body so clients can back off proportionally."""
    sched, _be = _scheduler(lm, num_blocks=4)
    fe = serving.start_frontend(sched)
    try:
        conn = http.client.HTTPConnection("127.0.0.1", fe.port,
                                          timeout=30)
        # 8 prompt + 24 new = 32 slots -> 8 blocks, pool holds 4
        conn.request("POST", "/v1/generate",
                     json.dumps({"model": "lm",
                                 "prompt": list(range(1, 9)),
                                 "max_new_tokens": 24}),
                     {"Content-Type": "application/json"})
        resp = conn.getresponse()
        assert resp.status == 429
        assert int(resp.getheader("Retry-After")) >= 1
        body = json.loads(resp.read().decode())
        assert body["type"] == "CacheExhaustedError"
        assert 0.0 <= body["kv_cache_occupancy"] <= 1.0
        assert body["kv_cache_blocks_total"] == 4
        assert isinstance(body["kv_cache_blocks_free"], int)
        # the shed took nothing: the lane still serves
        assert sched.generate("lm", [5, 6], max_new_tokens=4)
    finally:
        fe.close()
        sched.close()


def test_kv_alloc_chaos_site(lm):
    sched, _ = _scheduler(lm)
    with chaos.inject("serving.kv_alloc", "raise", prob=1.0, seed=7,
                      limit=1) as inj:
        with pytest.raises(MXNetError):
            sched.generate("lm", [1, 2, 3], max_new_tokens=4, timeout=30)
    assert inj.fires == 1
    assert sched.generate("lm", [1, 2, 3], max_new_tokens=4)
    sched.close()


# ------------------------------------------------- iteration-level admit

def test_iteration_level_admission(lm):
    """A request submitted while another is mid-generation joins the
    next decode step: some step ran with BOTH sequences in the batch."""
    sched, _ = _scheduler(lm)
    r1 = sched.submit("lm", np.array([1, 2, 3], np.int32),
                      max_new_tokens=24)
    # let r1 enter decode, then submit r2 mid-generation
    deadline = time.monotonic() + 10
    while sched.stats("lm")["steps"] < 2 and time.monotonic() < deadline:
        time.sleep(0.002)
    assert sched.stats("lm")["steps"] >= 2, "r1 never started decoding"
    r2 = sched.submit("lm", np.array([9, 8], np.int32),
                      max_new_tokens=24)
    assert len(r1.result(timeout=30)) == 24
    assert len(r2.result(timeout=30)) == 24
    st = sched.stats("lm")
    assert st["max_step_rows"] >= 2, \
        "r2 never shared a decode step with r1 (no iteration-level admission)"
    # and joining mid-flight never changed r2's tokens: parity again
    assert r2.generated == sched.generate("lm", [9, 8], max_new_tokens=24)
    sched.close()


def test_tokens_reach_their_streams_beside_the_next_device_call(lm):
    """A decode step's tokens are held until the loop's next device call
    is on its way, and no longer: where a call goes on to wait for its
    logits some stream is still owed a token, when it has them none is;
    a first token is in its stream at once; the streams end with exactly
    what was generated, in order."""
    sched, be = _scheduler(lm)
    reqs, owed = [], []
    fetch = be._fetch

    def held():
        return sum(len(r.generated) - r.released for r in reqs)

    def watched(*args):
        before = held()
        out = fetch(*args)
        owed.append((before, held()))
        return out

    be._fetch = watched
    reqs.append(sched.submit("lm", np.array([1, 2, 3], np.int32),
                             max_new_tokens=12))
    reqs.append(sched.submit("lm", np.array([9, 8], np.int32),
                             max_new_tokens=7))
    streams = [list(r.tokens(timeout=30)) for r in reqs]
    assert streams == [r.generated for r in reqs]
    assert [len(s) for s in streams] == [12, 7]
    assert len(owed) >= 2 + 11
    assert all(after == 0 for _, after in owed)
    assert max(before for before, _ in owed) == 2
    assert be.beside_device is None
    sched.close()


# ------------------------------------------------------------- chaos

def test_decode_fault_retries_without_corruption(lm):
    """A seeded mid-generation decode fault is retried; every live
    sequence's output stays bitwise identical to a no-chaos run —
    failed dispatches never write the cache."""
    prompts = [np.array([1, 2, 3], np.int32),
               np.array([7, 5], np.int32),
               np.array([11, 12, 13, 14], np.int32)]
    sched, _ = _scheduler(lm)
    clean = [sched.generate("lm", p, max_new_tokens=12) for p in prompts]
    sched.close()

    sched2, _ = _scheduler(lm)
    errors = sched2._fam["errors"].labels("lm")
    # limit=2 keeps any fire run inside the 3-attempt retry budget
    with chaos.inject("serving.decode", "raise", prob=0.3, seed=13,
                      limit=2) as inj:
        reqs = [sched2.submit("lm", p, max_new_tokens=12)
                for p in prompts]
        outs = [r.result(timeout=60) for r in reqs]
    assert inj.fires > 0, "seeded chaos never fired"
    assert errors.value >= inj.fires
    assert outs == clean, \
        "decode retries corrupted another sequence's cache blocks"
    sched2.close()


# ------------------------------------------------------------ streaming

def _raw_generate(port, payload, read_lines=None):
    """Speak chunked HTTP by hand on a raw socket so the test controls
    exactly how much is read (http.client buffers eagerly)."""
    body = json.dumps(payload).encode()
    sock = socket.create_connection(("127.0.0.1", port), timeout=30)
    sock.sendall(b"POST /v1/generate HTTP/1.1\r\n"
                 b"Host: t\r\nContent-Type: application/json\r\n"
                 b"Content-Length: %d\r\n\r\n%s" % (len(body), body))
    buf = b""
    lines = []
    while read_lines is None or len(lines) < read_lines:
        data = sock.recv(4096)
        if not data:
            break
        buf += data
        # (not the header's end behind a request id that ends in 0)
        if b"\r\n0\r\n\r\n" in buf and read_lines is None:
            break
        lines = [l for l in buf.split(b"\n") if l.strip().startswith(b"{")]
    return sock, buf


def test_streaming_round_trip(lm):
    sched, _ = _scheduler(lm)
    fe = serving.start_frontend(sched)
    try:
        conn = http.client.HTTPConnection("127.0.0.1", fe.port,
                                          timeout=30)
        conn.request("POST", "/v1/generate",
                     json.dumps({"model": "lm", "prompt": [3, 9, 1, 7],
                                 "max_new_tokens": 6}),
                     {"Content-Type": "application/json"})
        resp = conn.getresponse()
        assert resp.status == 200
        assert resp.getheader("Content-Type") == "application/x-ndjson"
        assert resp.getheader("X-MXTPU-Request-Id")
        lines = [json.loads(l) for l in
                 resp.read().decode().strip().split("\n")]
        tail = lines[-1]
        assert tail["done"] and tail["finish_reason"] == "length"
        assert [l["token"] for l in lines[:-1]] == tail["tokens"]
        assert tail["tokens"] == sched.generate("lm", [3, 9, 1, 7],
                                                max_new_tokens=6)
        # typed errors still map to HTTP statuses pre-stream
        conn2 = http.client.HTTPConnection("127.0.0.1", fe.port,
                                           timeout=30)
        conn2.request("POST", "/v1/generate",
                      json.dumps({"model": "nope", "prompt": [1]}),
                      {"Content-Type": "application/json"})
        assert conn2.getresponse().status == 404
    finally:
        fe.close()
        sched.close()


def test_a_streamed_token_is_one_write(lm, monkeypatch):
    """Every write to the socket is a send and a wake-up of the reader:
    a token's chunk (length, line, CRLF) goes out whole, the first by
    the handler's unbuffered wfile and the others by the stream writer
    (several in one send only where it fell behind), and the stream
    still parses as chunked HTTP."""
    import socketserver

    writes = []
    real_write = socketserver._SocketWriter.write
    real_send = socket.socket.send

    def write(self, b):
        writes.append(bytes(b))
        return real_write(self, b)

    def send(self, b, *flags):
        writes.append(bytes(b))
        return real_send(self, b, *flags)

    monkeypatch.setattr(socketserver._SocketWriter, "write", write)
    monkeypatch.setattr(socket.socket, "send", send)
    sched, _ = _scheduler(lm)
    fe = serving.start_frontend(sched)
    try:
        with chaos.inject("serving.decode", "delay", prob=1.0, seed=1,
                          delay=0.02):
            sock, buf = _raw_generate(fe.port, {"model": "lm",
                                                "prompt": [3, 9, 1, 7],
                                                "max_new_tokens": 5})
        sock.close()
    finally:
        fe.close()
        sched.close()
    chunks = [w for w in writes if b'{"token"' in w]
    assert 2 <= len(chunks) <= 5       # the handler's one, the writer's
    assert chunks[0].count(b'{"token"') == 1
    lines = []
    for w in chunks:
        while w and not w.startswith(b"0\r\n"):
            size, _, w = w.partition(b"\r\n")
            line, w = w[:int(size, 16)], w[int(size, 16):]
            assert w.startswith(b"\r\n") and line.endswith(b"\n")
            w = w[2:]
            lines.append(json.loads(line))
    assert [sorted(l) for l in lines[:5]] == [["token"]] * 5
    assert lines[5]["done"] and len(lines) == 6
    body = buf.split(b"\r\n\r\n", 1)[1]
    assert body.endswith(b"0\r\n\r\n")


def test_streaming_disconnect_frees_blocks(lm):
    """A client that drops mid-stream cancels the request; the decode
    loop retires the sequence and frees its cache blocks."""
    sched, be = _scheduler(lm)
    fe = serving.start_frontend(sched)
    try:
        with chaos.inject("serving.decode", "delay", prob=1.0, seed=1,
                          delay=0.05):
            sock, buf = _raw_generate(
                fe.port, {"model": "lm", "prompt": [5, 2],
                          "max_new_tokens": 40}, read_lines=2)
            assert b"200" in buf.split(b"\r\n", 1)[0]
            assert be.cache.stats()["used"] > 0
            sock.close()                       # client disconnect
            deadline = time.monotonic() + 15
            while (be.cache.stats()["used"] and
                   time.monotonic() < deadline):
                time.sleep(0.01)
        assert be.cache.stats()["used"] == 0, \
            "disconnect leaked KV-cache blocks"
        # the lane still serves after the disconnect
        assert sched.generate("lm", [1, 2], max_new_tokens=3)
    finally:
        fe.close()
        sched.close()


# ------------------------------------------------------------- hot swap

def test_hot_swap_reprefills_live_sequences(lm, monkeypatch):
    """A swap mid-generation re-prefills live sequences on the new
    backend (same weights here, so the token stream is unchanged) and
    the old cache is no longer written.

    The generation loop takes ``dispatch_lock`` again the moment it has
    released it, and ``threading.Lock`` is not fair: a swap that waits
    for the lock gets it by the scheduler's luck, or when the lane goes
    idle (alone on this machine the swap landed after the last token in
    6 runs of 7, and the test read 0 re-prefills).  So the loop here
    pauses between its iterations, which is the window a swap lands in;
    what is tested is what happens once it has landed."""
    cfg, params = lm
    sched, be1 = _scheduler(lm)
    clean = sched.generate("lm", [1, 2, 3], max_new_tokens=16)
    base = sched.stats("lm")["steps"]      # lane counters are cumulative
    iterate = sched._iterate

    def iterate_then_pause(name, lane):
        iterate(name, lane)
        time.sleep(0.02)

    monkeypatch.setattr(sched, "_iterate", iterate_then_pause)
    with chaos.inject("serving.decode", "delay", prob=1.0, seed=1,
                      delay=0.02):
        req = sched.submit("lm", np.array([1, 2, 3], np.int32),
                           max_new_tokens=16)
        deadline = time.monotonic() + 10
        while (sched.stats("lm")["steps"] < base + 2
               and time.monotonic() < deadline):
            time.sleep(0.002)
        assert sched.stats("lm")["active"] == 1, \
            "the sequence should still be mid-generation at swap time"
        be2 = serving.LMBackend(params, cfg, block_size=4, num_blocks=64)
        sched.swap("lm", be2)
        out = req.result(timeout=60)
    assert out == clean, "hot swap changed the token stream"
    reprefills = sched._fam["reprefills"].labels("lm")
    assert reprefills.value >= 1
    assert be2.cache.stats()["used"] == 0
    sched.close()


# ------------------------------------------------------------- watchdog

def test_watchdog_has_inter_token_rule():
    from mxnet_tpu.observability import watchdog
    rules = {r.name: r for r in watchdog.default_rules()}
    rule = rules["inter_token_p99"]
    assert rule.metric == "generation_inter_token_seconds"
    assert rule.stat == "p99"
