"""A full decode batch runs one step ahead (serving/generation.py):
``LMBackend.decode`` queues the next step, fed by the device's own
greedy ids, before it waits for this step's logits.

- **Same streams**: with run-ahead every request's tokens equal the
  serial path's, token for token, over a mix in which rows finish, one
  is cancelled and requests are admitted into a full and a part-full
  batch.
- **The first-token rule**: no step is queued whenever the lane has a
  free slot or a row ends in the step being answered, so no prefill is
  ever dispatched behind a queued step.
- **A call that asks for another step** drops the queued one, counts
  it, and leaves the pool as the serial path does.
- **Faults**: a chaos fault before the dispatch is retried and drops
  the queue; one at the logits fails the live sequences, leaves no
  queued step, and the lane serves on.
- **A subclass with the four-argument ``decode``** (the benchmark's
  wrapper) sees one call a step, numpy in, numpy logits out.
"""

import threading
import time

import numpy as np
import pytest

from mxnet_tpu import chaos, serving
from mxnet_tpu.models import transformer as tfm
from mxnet_tpu.observability import metrics as om

VOCAB, SEQ_LEN, EMBED, HEADS, LAYERS = 64, 48, 16, 2, 2
FULL = 4                     # the largest decode bucket


@pytest.fixture(scope="module")
def lm():
    cfg = tfm.lm_config(num_classes=VOCAB, seq_len=SEQ_LEN,
                        num_embed=EMBED, num_heads=HEADS,
                        num_layers=LAYERS)
    return cfg, tfm.init_lm_params(cfg, seed=0)


def _backend(lm, model, cls=serving.LMBackend):
    cfg, params = lm
    return cls(params, cfg, block_size=4, num_blocks=64, model=model)


def _scheduler(lm, model, cls=serving.LMBackend, ahead=True):
    sched = serving.GenerationScheduler()
    if not ahead:               # the serial path: the rule says never
        sched._may_run_ahead = lambda lane, live: False
    be = _backend(lm, model, cls)
    sched.register("lm", be, decode_buckets=[1, 2, FULL],
                   prefill_buckets=[8, 16])
    sched.warmup("lm")
    return sched, be


def _count(kind, model):
    return om.REGISTRY.get(
        "generation_decode_ahead_%s_total" % kind).labels(model).value


def _submit_together(sched, requests):
    """Submit while the loop cannot iterate, so that one iteration
    admits them all (up to the batch's free slots)."""
    with sched._lanes["lm"].entry.dispatch_lock:
        return [sched.submit("lm", np.asarray(p, np.int32),
                             max_new_tokens=m) for p, m in requests]


def _wait(cond, what, seconds=30):
    deadline = time.monotonic() + seconds
    while not cond():
        assert time.monotonic() < deadline, what
        time.sleep(0.002)


MIX = [([1, 2, 3], 10), ([9, 8], 17), ([4, 5, 6, 7], 25), ([11], 30),
       ([3, 1, 4, 1, 5], 22), ([2, 7, 1, 8], 9), ([6, 6, 6], 14)]
CANCELLED = 3


class Cancelling(serving.LMBackend):
    """Cancels ``victim`` from the loop's own thread once it has six
    tokens: as a client going away mid-flight, but at a known step (and
    after the loop chose to run ahead: the queued step is dropped).
    Parks the loop, once, at the decode call that ``first`` enters with
    two tokens, until the test has submitted the others: whatever the
    machine's load, they are admitted in one iteration, with ``first``
    at its third token."""

    victim = first = None

    def __init__(self, *args, **kwargs):
        serving.LMBackend.__init__(self, *args, **kwargs)
        self.parked, self.go_on = threading.Event(), threading.Event()

    def decode(self, *step):
        if (self.first is not None and not self.parked.is_set()
                and len(self.first.generated) == 2):
            self.parked.set()
            assert self.go_on.wait(30), "the test never let the loop go on"
        if self.victim is not None and len(self.victim.generated) >= 6:
            self.victim.cancel()
        return serving.LMBackend.decode(self, *step)


def test_streams_equal_the_serial_paths(lm):
    """Rows finish at different steps, MIX[3] is cancelled mid-flight,
    the first request is admitted into an empty batch, the next three
    fill it (three wait: admitted as rows end, the batch full till
    then).  The loop is parked while the six are submitted, so every
    run has the same schedule: the cancelled row's step is one that ran
    ahead (the batch full, the first request one short of its last
    token), and its queued step is the one that is dropped."""
    serial, _ = _scheduler(lm, "ahead_ref", ahead=False)
    want = [serial.generate("lm", p, max_new_tokens=m) for p, m in MIX]
    serial.close()
    assert _count("used", "ahead_ref") == 1      # the warm-up's own

    sched, be = _scheduler(lm, "ahead_mix", Cancelling)
    with sched._lanes["lm"].entry.dispatch_lock:     # before any step
        reqs = [sched.submit("lm", np.asarray(MIX[0][0], np.int32),
                             max_new_tokens=MIX[0][1])]
        be.first = reqs[0]
    assert be.parked.wait(30), "the first never started"
    assert len(reqs[0].generated) == 2
    reqs += [sched.submit("lm", np.asarray(p, np.int32),
                          max_new_tokens=m) for p, m in MIX[1:]]
    be.victim = reqs[CANCELLED]
    be.go_on.set()
    got = []
    for i, r in enumerate(reqs):
        if i == CANCELLED:
            _wait(lambda: r.done, "the cancelled request never ended")
            assert r.finish_reason == "cancelled"
            got.append(list(r.generated))
        else:
            got.append(r.result(timeout=60))
    for i, (g, w) in enumerate(zip(got, want)):
        if i == CANCELLED:
            assert len(g) == 7 and g == w[:7]
        else:
            assert g == w, "request %d differs from the serial path" % i
    assert _count("used", "ahead_mix") > 1, "no step ever ran ahead"
    assert _count("dropped", "ahead_mix") == 1   # the cancelled row's
    sched.close()
    assert be._ahead is None and be.cache.stats()["used"] == 0


class Recording(serving.LMBackend):
    """Notes, around every call, what the lane looked like and whether a
    step was queued."""

    lane = None

    def __init__(self, *args, **kwargs):
        serving.LMBackend.__init__(self, *args, **kwargs)
        self.events = []

    def prefill(self, tokens, length):
        self.events.append(("prefill", self._ahead is not None))
        return serving.LMBackend.prefill(self, tokens, length)

    def _dispatch_decode(self, tokens, positions, *rest):
        self.events.append(("dispatch", int(np.max(positions))))
        return serving.LMBackend._dispatch_decode(self, tokens, positions,
                                                  *rest)

    def decode(self, tokens, positions, block_tables, context_lens):
        live = list(self.lane.active) if self.lane else []
        ending = any(s.new_tokens + 1 >= s.req.max_new_tokens
                     for s in live)
        had = self._ahead is not None
        out = serving.LMBackend.decode(self, tokens, positions,
                                       block_tables, context_lens)
        self.events.append(("decode", len(live), ending, had,
                            self._ahead is not None))
        return out


def test_no_step_is_queued_where_a_request_could_be_admitted(lm):
    """The rule, seen from the backend: after a decode call a step is
    queued exactly when the batch is full and no row ended in the call;
    no prefill is ever dispatched with a step queued; a queued step is
    one more dispatch, in front of the call that is answered by it."""
    sched, be = _scheduler(lm, "ahead_rule", Recording)
    be.lane = sched._lanes["lm"]
    be.events = []
    used0 = _count("used", "ahead_rule")
    first = sched.submit("lm", np.array([5, 6], np.int32), max_new_tokens=6)
    reqs = [first] + _submit_together(
        sched, [([1, 2, 3], 12), ([9, 8], 19), ([4, 5, 6, 7], 8),
                ([11], 15), ([3, 1, 4], 11), ([2, 7], 16)])
    for r in reqs:
        r.result(timeout=60)
    sched.close()
    calls = [e for e in be.events if e[0] == "decode"]
    assert calls and not any(queued for kind, queued in
                             (e for e in be.events if e[0] == "prefill"))
    for _, rows, ending, _, queued in calls:
        assert queued == (rows == FULL and not ending), (rows, ending)
    answered = [had for _, _, _, had, _ in calls]
    assert any(answered) and not all(answered)
    # nothing was cancelled: every queued step was used, none dropped
    assert _count("used", "ahead_rule") - used0 == sum(answered)
    assert _count("dropped", "ahead_rule") == 0
    # a call answered by the queue dispatches at most the step after it;
    # any other call dispatches its own step first
    kinds = [e[0] for e in be.events if e[0] != "prefill"]
    at = 0
    for _, _, _, had, queued in calls:
        end = kinds.index("decode", at)
        assert end - at == (not had) + queued
        at = end + 1
    steps = om.REGISTRY.get("generation_decode_steps_total")
    assert steps.labels("lm").value >= len(calls)


def _two_rows(lm, model):
    """A backend with two prefilled sequences and the decode step over
    both (bucket 2)."""
    be = _backend(lm, model)
    tables = np.zeros((2, be.max_blocks_per_seq), np.int32)
    lengths = (5, 3)
    for i, length in enumerate(lengths):
        be.cache.allocate("s%d" % i, 12)
        _, k, v, _ = be.prefill(np.arange(1 + i, 9 + i, dtype=np.int32),
                                length)
        be.cache.write_prefill("s%d" % i, k, v, length)
        tables[i] = be.cache.block_table("s%d" % i, be.max_blocks_per_seq)
    positions = np.array(lengths, np.int32)
    return be, [np.array([7, 9], np.int32), positions, tables,
                positions + 1]


def _pools(cache):
    return np.array(cache.k_pages), np.array(cache.v_pages)


def test_a_call_for_another_step_drops_the_queued_one(lm):
    """Step 1 queues step 2 (fed step 1's ids); the next call feeds
    row 1 another token than the one chosen: the queued step is dropped
    and counted, the call is dispatched afresh, its logits and the pool
    afterwards are bit for bit the serial path's.  Asked for the queued
    step itself, the call is answered by it, equal to the serial
    path's too."""
    serial, step = _two_rows(lm, "ahead_serial")
    ahead, _ = _two_rows(lm, "ahead_drop")
    ahead.run_ahead = True
    one = ahead.decode(*step)
    ahead.run_ahead = False
    assert ahead._ahead is not None
    np.testing.assert_array_equal(one[0], serial.decode(*step)[0])
    np.testing.assert_array_equal(ahead.greedy_ids, one[0].argmax(axis=1))
    follow = [ahead.greedy_ids, step[1] + 1, step[2], step[3] + 1]
    other = [follow[0].copy()] + follow[1:]
    other[0][1] = (other[0][1] + 1) % VOCAB
    got = ahead.decode(*other)
    assert ahead._ahead is None
    assert (_count("dropped", "ahead_drop"),
            _count("used", "ahead_drop")) == (1, 0)
    np.testing.assert_array_equal(got[0], serial.decode(*other)[0])
    for a, b in zip(_pools(ahead.cache), _pools(serial.cache)):
        assert np.array_equal(a, b)
    assert ahead.cache.length("s0") == serial.cache.length("s0") == 7

    again, _ = _two_rows(lm, "ahead_used")
    again.run_ahead = True
    again.decode(*step)
    again.run_ahead = False
    got = again.decode(*follow)
    assert (_count("dropped", "ahead_used"),
            _count("used", "ahead_used")) == (0, 1)
    serial2, _ = _two_rows(lm, "ahead_serial2")
    serial2.decode(*step)
    np.testing.assert_array_equal(got[0], serial2.decode(*follow)[0])
    for a, b in zip(_pools(again.cache), _pools(serial2.cache)):
        assert np.array_equal(a, b)


def test_a_dropped_steps_write_stays_in_its_rows_own_slot(lm):
    """Row 1 goes away while a step is queued: the dropped step's write
    is the one slot that differs from the serial pool, in row 1's own
    block at a position no step has read; the row that goes on gets the
    serial path's logits, and so does whoever gets row 1's blocks
    next."""
    serial, step = _two_rows(lm, "ahead_gone_ref")
    ahead, _ = _two_rows(lm, "ahead_gone")
    ahead.run_ahead = True
    ahead.decode(*step)
    ahead.run_ahead = False
    serial.decode(*step)
    alone = [np.array([ahead.greedy_ids[0]], np.int32), step[1][:1] + 1,
             step[2][:1], step[3][:1] + 1]
    np.testing.assert_array_equal(ahead.decode(*alone)[0],
                                  serial.decode(*alone)[0])
    assert _count("dropped", "ahead_gone") == 1
    block, offset = step[2][1][4 // 4], 4 % 4        # row 1, position 4
    for a, b in zip(_pools(ahead.cache), _pools(serial.cache)):
        differs = np.argwhere((a != b).any(axis=(0, 3)))
        assert differs.tolist() == [[block, offset]]
    outs = []
    for be in (ahead, serial):
        be.cache.free("s1")
        be.cache.allocate("new", 12)
        table = be.cache.block_table("new", be.max_blocks_per_seq)
        assert block in table
        _, k, v, _ = be.prefill(np.arange(20, 28, dtype=np.int32), 4)
        be.cache.write_prefill("new", k, v, 4)
        outs.append(be.decode([3], [4], table[None], [5])[0])
    np.testing.assert_array_equal(*outs)


LONG = [([1, 2, 3], 20), ([9, 8], 20), ([4, 5, 6, 7], 20), ([11], 20)]


def test_a_fault_before_the_dispatch_is_retried_and_drops_the_queue(lm):
    """The ``serving.decode`` chaos site fires before the device call,
    with a step queued: the call is retried as ever, the queued step is
    dropped and counted, and every stream equals the clean run's."""
    clean_sched, _ = _scheduler(lm, "ahead_clean")
    clean = [r.result(timeout=60)
             for r in _submit_together(clean_sched, LONG)]
    clean_sched.close()
    sched, be = _scheduler(lm, "ahead_chaos")
    errors = sched._fam["errors"].labels("lm")
    before = errors.value
    with chaos.inject("serving.decode", "raise", prob=0.3, seed=13,
                      limit=2) as inj:
        outs = [r.result(timeout=60)
                for r in _submit_together(sched, LONG)]
    assert inj.fires > 0, "seeded chaos never fired"
    assert errors.value - before >= inj.fires
    assert outs == clean
    assert 1 <= _count("dropped", "ahead_chaos") <= inj.fires
    assert _count("used", "ahead_chaos") > 1
    sched.close()
    assert be._ahead is None


class BrokenFetch(serving.LMBackend):
    """From its ``fail_from``-th decode step on, the copy of a step's
    logits fails, once for every attempt the loop makes."""

    fail_from = None

    def __init__(self, *args, **kwargs):
        serving.LMBackend.__init__(self, *args, **kwargs)
        self.fetches, self.ran_ahead = 0, []

    def _fetch(self, phase, *args, **kwargs):
        if phase == "decode":
            self.fetches += 1
            if (self.fail_from is not None
                    and self.fetches >= self.fail_from
                    and len(self.ran_ahead) <= serving.scheduler.default_retries()):
                self.ran_ahead.append(self.run_ahead)
                raise RuntimeError("device fault at the logits copy")
        return serving.LMBackend._fetch(self, phase, *args, **kwargs)


def test_a_fault_at_the_logits_fails_the_live_and_the_lane_serves_on(lm):
    """With the batch full and a step queued behind it, the logits of
    step 6 cannot be read, at every retry: the live sequences fail, each
    attempt's queued step is dropped and none is left behind, every
    block comes back, and the next request is served as if nothing had
    happened."""
    sched, be = _scheduler(lm, "ahead_fetch", BrokenFetch)
    clean = sched.generate("lm", [1, 2, 3], max_new_tokens=8)
    be.fail_from = be.fetches + 6
    for r in _submit_together(sched, LONG):
        with pytest.raises(Exception, match="logits copy"):
            r.result(timeout=30)
        assert 1 <= len(r.generated) <= 6
    attempts = serving.scheduler.default_retries() + 1
    assert be.ran_ahead == [True] * attempts
    _wait(lambda: be.cache.stats()["used"] == 0, "blocks never came back")
    assert be._ahead is None
    assert _count("dropped", "ahead_fetch") == attempts
    assert sched.generate("lm", [1, 2, 3], max_new_tokens=8) == clean
    sched.close()


def test_a_subclass_with_the_four_argument_decode_sees_every_step(lm):
    """The benchmark's wrapper, reduced: it overrides ``decode`` with
    the present signature and calls the base.  It sees one call a
    decode step, numpy arguments, a numpy ``out[0]`` whose rows belong
    to those arguments (the served token is its argmax, and the token
    the next call feeds that row), whether the step was queued or
    not."""
    seen = []

    class Wrapped(serving.LMBackend):
        def decode(self, tokens, positions, block_tables, context_lens):
            out = serving.LMBackend.decode(self, tokens, positions,
                                           block_tables, context_lens)
            seen.append((tokens, positions, block_tables, context_lens,
                         out))
            return out

    sched, be = _scheduler(lm, "ahead_wrapped", Wrapped)
    del seen[:]
    reqs = _submit_together(sched, LONG)
    streams = [r.result(timeout=60) for r in reqs]
    steps = sched.stats("lm")["steps"]
    sched.close()
    assert len(seen) == steps
    assert _count("used", "ahead_wrapped") > 1
    served = {}                 # (position, token fed) -> rows of logits
    for tokens, positions, tables, lens, out in seen:
        for a in (tokens, positions, tables, lens, out[0]):
            assert type(a) is np.ndarray
        assert len(out) == 4 and out[0].shape == (len(tokens), VOCAB)
        assert out[0].dtype == np.float32 and tokens.dtype == np.int32
        for i in range(len(tokens)):
            served.setdefault((int(positions[i]), int(tokens[i])),
                              []).append(out[0][i])
    for (prompt, _), stream in zip(LONG, streams):
        for j in range(1, len(stream)):
            rows = served[len(prompt) - 1 + j, stream[j - 1]]
            assert any(int(row.argmax()) == stream[j] for row in rows)


@pytest.mark.parametrize("kept", ["nothing", "the array", "a view"])
def test_a_steps_logits_are_written_again_only_once_let_go(lm, kept):
    """``LMBackend._host_logits``: a decode step's logits come in the
    host array an earlier step's came in where the caller kept neither
    that array nor a view of it (the benchmark's wrapper of a large
    vocabulary keeps a copied part), and in a new one where it did:
    what a caller holds is never written under it."""
    be, args = _two_rows(lm, "handed_" + kept.replace(" ", "_"))
    first = be.decode(*args)[0]
    held = {"nothing": None, "the array": first, "a view": first[1]}[kept]
    was, where = first.copy(), first.ctypes.data
    del first
    args[0], args[1], args[3] = (be.greedy_ids, args[1] + 1, args[3] + 1)
    second = be.decode(*args)[0]
    assert not np.array_equal(second, was)
    assert (second.ctypes.data == where) == (kept == "nothing")
    if kept == "the array":
        np.testing.assert_array_equal(held, was)
    if kept == "a view":
        np.testing.assert_array_equal(held, was[1])
