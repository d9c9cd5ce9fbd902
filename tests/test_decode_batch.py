"""The decode batch is kept, not rebuilt (serving/generation.py): a
sequence's block-table row is made once, when it joins the batch; the
lane puts the live rows together only when the batch gained or lost a
sequence; between such steps ``LMBackend.decode`` is handed the very
table array the last call got, and the table crosses to the device
(and its rows' state slots are looked up) once a table, not once a
step.

- **Same work**: over a mix in which requests are admitted into a full
  and a part-full batch, rows end at different steps, one is cancelled
  mid-flight and one stops at its ``eos_id``, every decode call's four
  arguments equal, value for value, what the loop built step by step
  before (``rebuilt``, kept here as the reference), the state slots a
  step is handed equal the per-step lookup, and the served tokens equal
  those of a backend that is handed the rebuilt arguments: with
  run-ahead on and off, for a model without and one with recurrent
  state.
- **Built once a sequence**: ``generation_block_table_rows_built_total``
  counts sequences started plus resumes, and the table object changes
  exactly when the batch's membership does.
- **What was handed over is never written**: every argument object of
  every call still equals the copy taken when it was handed over.
- **The hazard**: a row replaced, between two steps, by a sequence with
  the old row's blocks, position + 1 and token is not answered by the
  step that was queued for the old batch.
- **Fresh rows**: a hot swap and a ``RecurrentStateHazard`` resume give
  every live sequence a new row (and slot); ``cache.free`` forgets
  them; an unknown ``seq_id`` still raises.
"""

import threading
import time

import numpy as np
import pytest

import jax.numpy as jnp

from mxnet_tpu import chaos, serving
from mxnet_tpu.base import MXNetError
from mxnet_tpu.models import gated_delta_moe as gm
from mxnet_tpu.models import transformer as tfm
from mxnet_tpu.observability import metrics as om

VOCAB, SEQ_LEN = 64, 48
FULL = 4                     # the largest decode bucket
KINDS = ["dense", "stateful"]
# the hybrid decoder of tests/test_gated_delta_moe.py at its tiny size:
# three layers that keep recurrent state to one that keeps keys and
# values, so a sequence has a state slot beside its blocks
HYBRID = {
    "hidden_size": 32, "num_hidden_layers": 4, "full_attention_interval": 4,
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
    "partial_rotary_factor": 0.25, "rope_theta": 10000000,
    "rope_scaling": None, "linear_conv_kernel_dim": 4,
    "linear_key_head_dim": 8, "linear_num_key_heads": 2,
    "linear_num_value_heads": 4, "linear_value_head_dim": 8,
    "num_experts": 16, "num_experts_per_tok": 4, "moe_intermediate_size": 16,
    "shared_expert_intermediate_size": 16, "norm_topk_prob": True,
    "rms_norm_eps": 1e-6, "vocab_size": VOCAB}


@pytest.fixture(scope="module")
def models():
    dense = tfm.lm_config(num_classes=VOCAB, seq_len=SEQ_LEN, num_embed=16,
                          num_heads=2, num_layers=2)
    hybrid = gm.lm_config(HYBRID, seq_len=SEQ_LEN, held=(0, 16))
    return {"dense": (dense, tfm.init_lm_params(dense, seed=0)),
            "stateful": (hybrid, gm.init_params(hybrid, 0, jnp.float32,
                                                0.3))}


def _backend(models, kind, name, cls=serving.LMBackend):
    cfg, params = models[kind]
    if kind == "dense":
        return cls(params, cfg, block_size=4, num_blocks=64, model=name)
    return cls(params, definition=gm.lm_definition(cfg, jnp.float32),
               block_size=4, num_blocks=64, model=name, state_slots=FULL)


def _scheduler(models, kind, name, cls=serving.LMBackend, ahead=True,
               buckets=(1, 2, FULL)):
    sched = serving.GenerationScheduler(name=name)
    if not ahead:               # the serial path: the rule says never
        sched._may_run_ahead = lambda lane, live: False
    be = _backend(models, kind, name, cls)
    sched.register(name, be, decode_buckets=list(buckets),
                   prefill_buckets=[8, 16, 32])
    sched.warmup(name)
    be.lane = sched._lanes[name]
    be.calls = []               # the warm-up's own are not the loop's
    return sched, be


def _counter(family, name):
    return om.REGISTRY.get(family).labels(name).value


def _rows_built(name):
    return _counter("generation_block_table_rows_built_total", name)


def _wait(cond, what, seconds=60):
    deadline = time.monotonic() + seconds
    while not cond():
        assert time.monotonic() < deadline, what
        time.sleep(0.002)


def rebuilt(lane, backend):
    """A decode call's four arguments as ``_decode_step`` made them
    every step before the batch was kept: every live row's table asked
    of the cache anew."""
    live = lane.active
    bucket = lane.entry.pick_bucket(len(live))
    tokens = np.zeros(bucket, dtype=np.int32)
    positions = np.zeros(bucket, dtype=np.int32)
    context = np.ones(bucket, dtype=np.int32)
    tables = np.zeros((bucket, backend.max_blocks_per_seq), dtype=np.int32)
    for i, seq in enumerate(live):
        tokens[i] = seq.last_token
        positions[i] = seq.length
        context[i] = seq.length + 1
        tables[i] = backend.cache.block_table(
            seq.seq_id, backend.max_blocks_per_seq)
    return tokens, positions, tables, context


class Call(object):
    """One decode call as a recording backend saw it."""

    def __init__(self, be, args):
        self.args = args                            # the objects
        self.copies = [np.array(a) for a in args]   # their values then
        self.members = tuple(be.lane.active)
        self.rebuilt = rebuilt(be.lane, be)
        self.slots_looked_up = (
            be.cache.state_slots(self.rebuilt[2], self.rebuilt[1])
            if be.cache.state else None)
        self.queued_before = be._ahead
        self.used_before = _counter("generation_decode_ahead_used_total",
                                    be.model)

    def returned(self, be):
        self.answered_by_queue = _counter(
            "generation_decode_ahead_used_total", be.model) \
            > self.used_before
        self.queued_after = be._ahead
        table = be._table
        self.table_kept = table.host
        self.slots_handed = None if table.slots is None \
            else np.array(table.slots)


class Recording(serving.LMBackend):
    """Keeps every decode call of the loop: the argument objects, a copy
    of their values, the lane's members and what the step-by-step
    construction would have handed over at that moment.  ``before`` (a
    function of this backend) runs in the loop's thread ahead of every
    call, after the loop has decided whether to run ahead."""

    lane = before = None
    calls = ()

    def handed(self, args):
        """What the step runs on: what the loop handed over."""
        return args

    def decode(self, *args):
        if self.lane is None:           # the warm-up
            return serving.LMBackend.decode(self, *args)
        if self.before is not None:
            self.before(self)
        call = Call(self, self.handed(args))
        out = serving.LMBackend.decode(self, *call.args)
        call.returned(self)
        self.calls.append(call)
        return out


class Rebuilding(Recording):
    """The reference system: whatever it is handed, the step runs on
    the arguments built step by step, new arrays every call."""

    def handed(self, args):
        return rebuilt(self.lane, self)


# prompts and budgets: seven requests for four rows, so three wait and
# are admitted as rows end; the batch drains through buckets 4, 2 and 1
MIX = [([1, 2, 3], 10), ([9, 8], 17), ([4, 5, 6, 7], 25), ([11], 30),
       ([3, 1, 4, 1, 5], 22), ([2, 7, 1, 8], 4), ([6, 6, 6], 14)]
CANCELLED, STOPPED = 3, 2


def _serve_mix(models, kind, name, cls, ahead, eos=None):
    """Serve MIX with everything submitted before the loop's first
    iteration (so every run has the same schedule), request CANCELLED
    cancelled from the loop's own thread at the first call, once it has
    nine tokens, that the rule lets run ahead (whether this run does or
    not: a step is queued behind that call where any is),
    request STOPPED given ``eos`` as its ``eos_id``.  Returns the
    streams, the backend and the scheduler (closed)."""
    sched, be = _scheduler(models, kind, name, cls, ahead)
    reqs = []

    def cancel_the_victim(_):
        if (len(reqs[CANCELLED].generated) >= 9
                and serving.GenerationScheduler._may_run_ahead(
                    sched, be.lane, be.lane.active)):
            reqs[CANCELLED].cancel()

    be.before = cancel_the_victim
    with be.lane.entry.dispatch_lock:
        for i, (prompt, budget) in enumerate(MIX):
            reqs.append(sched.submit(
                name, np.asarray(prompt, np.int32), max_new_tokens=budget,
                eos_id=eos if i == STOPPED else None))
    for r in reqs:
        _wait(lambda: r.done, "a request never ended")
    sched.close()
    assert [r.finish_reason for r in reqs] == [
        "cancelled" if i == CANCELLED else
        "stop" if i == STOPPED and eos is not None else "length"
        for i in range(len(MIX))]
    assert be._ahead is None and be.cache.stats()["used"] == 0
    # an idle lane holds on to no sequence
    assert be.lane.seated == [] and be.lane.tables is None
    return [list(r.generated) for r in reqs], be, sched


@pytest.fixture(scope="module")
def reference_streams(models):
    """The step-by-step rebuild's streams over MIX, serial, a model
    kind: what every kept-batch run has to serve.  The ``eos_id`` is the
    token request STOPPED serves eighth, if it serves it then for the
    first time: learnt from a run without one."""
    out = {}
    for kind in KINDS:
        free, _, _ = _serve_mix(models, kind, "batch_ref_free_" + kind,
                                Rebuilding, ahead=False)
        stream = free[STOPPED]
        at = next(i for i in range(7, len(stream))
                  if stream[i] not in stream[:i])
        eos = stream[at]
        want, _, _ = _serve_mix(models, kind, "batch_ref_" + kind,
                                Rebuilding, ahead=False, eos=eos)
        assert want[STOPPED] == stream[:at + 1]
        assert 9 < len(want[CANCELLED]) < MIX[CANCELLED][1]
        out[kind] = (eos, want)
    return out


@pytest.fixture(scope="module")
def kept_runs(models, reference_streams):
    """MIX served by the kept batch, recorded: a model kind and
    run-ahead on or off."""
    runs = {}

    def run(kind, ahead):
        if (kind, ahead) not in runs:
            name = "batch_%s_%s" % (kind, "ahead" if ahead else "serial")
            before = _rows_built(name)
            streams, be, sched = _serve_mix(
                models, kind, name, Recording, ahead,
                eos=reference_streams[kind][0])
            # read now: the counters are zeroed after every test
            counts = {"rows_built": _rows_built(name) - before}
            for what in ("used", "dropped"):
                counts[what] = _counter(
                    "generation_decode_ahead_%s_total" % what, name)
            runs[kind, ahead] = (streams, be, sched, counts)
        return runs[kind, ahead]

    return run


CASES = [(kind, ahead) for kind in KINDS for ahead in (True, False)]
IDS = ["%s-%s" % (kind, "ahead" if ahead else "serial")
       for kind, ahead in CASES]


@pytest.mark.parametrize("kind,ahead", CASES, ids=IDS)
def test_served_tokens_are_the_rebuilds(kept_runs, reference_streams, kind,
                                        ahead):
    streams, _, _, counts = kept_runs(kind, ahead)
    assert streams == reference_streams[kind][1]
    assert (counts["used"] > 1) == ahead        # one is the warm-up's own


@pytest.mark.parametrize("kind,ahead", CASES, ids=IDS)
def test_every_calls_arguments_are_the_rebuilds(kept_runs, kind, ahead):
    """Value for value the four arguments of every call, and the state
    slots the device was handed, are what the loop built and looked up
    every step before; the buckets 4, 2 and 1 all occur (a part-full
    bucket's pad rows read position 0, context 1, slot ``num_slots``)."""
    _, be, _, _ = kept_runs(kind, ahead)
    assert {len(c.args[0]) for c in be.calls} == {1, 2, FULL}
    assert any(len(c.members) < len(c.args[0]) for c in be.calls)
    for c in be.calls:
        for got, want in zip(c.copies, c.rebuilt):
            assert got.dtype == want.dtype == np.int32
            np.testing.assert_array_equal(got, want)
        assert c.table_kept is c.args[2]
        if kind == "stateful":
            np.testing.assert_array_equal(c.slots_handed,
                                          c.slots_looked_up)
            pad = np.arange(len(c.args[0])) >= len(c.members)
            assert (c.slots_handed[pad] == be.cache.num_slots).all()
            assert (c.slots_handed[~pad] < be.cache.num_slots).all()
        else:
            assert c.slots_handed is None


@pytest.mark.parametrize("kind,ahead", CASES, ids=IDS)
def test_a_row_is_built_once_a_sequence_and_the_table_once_a_change(
        kept_runs, kind, ahead):
    """One row a sequence started (none is resumed here); the table
    handed to ``decode`` is the very array of the call before exactly
    when the batch has the same members in the same order, and the
    three vectors are new arrays every call."""
    _, be, sched, counts = kept_runs(kind, ahead)
    assert counts["rows_built"] == len(MIX)
    assert sched.stats(be.model)["steps"] == len(be.calls) > 20
    changes = 0
    for prev, call in zip(be.calls, be.calls[1:]):
        same = call.members == prev.members
        assert (call.args[2] is prev.args[2]) == same
        changes += not same
        for i in (0, 1, 3):
            assert call.args[i] is not prev.args[i]
    # seven sequences joined and seven left, in fewer changes than that
    # (a row that ends is replaced in the same iteration) and far fewer
    # than there were steps
    assert 6 <= changes <= 2 * len(MIX)
    assert len({id(c.args[2]) for c in be.calls}) == changes + 1


@pytest.mark.parametrize("kind,ahead", CASES, ids=IDS)
def test_nothing_handed_over_is_written_afterwards(kept_runs, kind, ahead):
    """The queued step keeps references to a call's arguments and the
    CPU platform may read a numpy argument where it lies: at the end of
    the run every object that reached ``decode`` still holds the values
    it had then."""
    _, be, _, _ = kept_runs(kind, ahead)
    for c in be.calls:
        for obj, copy in zip(c.args, c.copies):
            assert type(obj) is np.ndarray
            np.testing.assert_array_equal(obj, copy)


@pytest.mark.parametrize("kind,ahead", CASES, ids=IDS)
def test_a_queued_step_answers_only_the_batch_it_was_queued_for(
        kept_runs, kind, ahead):
    """Across a change of membership no call is answered by a queued
    step: a step queued for the old batch (the cancelled row's: the
    loop had decided to run ahead before the cancel) is dropped with
    the table, and no other step is."""
    _, be, _, counts = kept_runs(kind, ahead)
    answered = stranded = 0
    for prev, call in zip(be.calls, be.calls[1:]):
        if call.members != prev.members:
            assert call.queued_before is None
            assert not call.answered_by_queue
            stranded += prev.queued_after is not None
        else:
            assert call.queued_before is prev.queued_after
            assert call.answered_by_queue == (prev.queued_after is not None)
        answered += call.answered_by_queue
    assert (answered > 0) == ahead
    assert stranded == (1 if ahead else 0)
    assert counts["dropped"] == stranded
    assert counts["used"] == answered + 1       # and the warm-up's own


# ----------------------------------------------------------------------
# the hazard: a successor with the old row's blocks, position and token


@pytest.mark.parametrize("kind", KINDS)
def test_a_replaced_row_is_never_answered_by_the_queued_step(models, kind):
    """Two rows fill the batch, both far from their end: every step runs
    ahead.  ``victim`` (the second row) is cancelled from the loop's own
    thread at the call it enters with K tokens, after the loop chose to
    run ahead, so the step after it is queued with the victim in it.
    The request that takes its place was made to look like it: its
    prompt is the victim's prompt and K tokens (so it gets the same
    number of blocks, the very blocks the victim freed, stands at the
    victim's position + 1 and feeds the token the victim would have
    fed): the next call's four arguments *equal* what was queued, value
    for value, and it still is dispatched afresh."""
    K = 5
    name = "batch_hazard_" + kind
    solo, _ = _scheduler(models, kind, name + "_solo", ahead=False,
                         buckets=(2,))
    prompt = [7, 3, 9]
    stream = solo.generate(name + "_solo", prompt, max_new_tokens=K + 1)
    solo.close()

    sched, be = _scheduler(models, kind, name, Recording, buckets=(2,))
    reqs = []

    def cancel_the_victim(_):
        if len(reqs[1].generated) == K and not reqs[1].cancelled:
            reqs[1].cancel()

    be.before = cancel_the_victim
    with be.lane.entry.dispatch_lock:
        reqs.append(sched.submit(name, np.array([5, 6], np.int32),
                                 max_new_tokens=30))
        reqs.append(sched.submit(name, np.asarray(prompt, np.int32),
                                 max_new_tokens=24))
        # the same horizon: 3 + 24 = (3 + K) + (24 - K) tokens
        reqs.append(sched.submit(
            name, np.asarray(prompt + stream[:K], np.int32),
            max_new_tokens=24 - K))
    for r in reqs:
        _wait(lambda: r.done, "a request never ended")
    sched.close()
    assert reqs[1].finish_reason == "cancelled"
    assert reqs[1].generated == stream[:K + 1]
    assert reqs[2].generated[0] == stream[K]

    at = next(i for i, c in enumerate(be.calls)
              if reqs[2] in [s.req for s in c.members])
    prev, call = be.calls[at - 1], be.calls[at]
    queued = prev.queued_after
    assert queued is not None, "the step behind the victim's was not queued"
    assert [s.req for s in prev.members] == reqs[:2]
    assert [s.req for s in call.members] == [reqs[0], reqs[2]]
    # what was queued for the old batch equals what the new batch asks
    for was, now in zip(queued.fed, call.copies):
        np.testing.assert_array_equal(np.asarray(was), now)
    assert call.args[2] is not prev.args[2]
    assert call.queued_before is None and not call.answered_by_queue
    assert _counter("generation_decode_ahead_dropped_total", name) == 1
    # and the successor is served what it is served alone
    alone, _ = _scheduler(models, kind, name + "_alone", ahead=False,
                          buckets=(2,))
    want = alone.generate(name + "_alone", prompt + stream[:K],
                          max_new_tokens=24 - K)
    alone.close()
    assert reqs[2].generated == want


# ----------------------------------------------------------------------
# fresh rows after a hot swap and after a state hazard


class Gate(object):
    """Lets a test stop the loop between two iterations (where a swap
    lands) and let it go on."""

    def __init__(self, sched):
        self.want, self.parked, self.go = (threading.Event(),
                                           threading.Event(),
                                           threading.Event())
        iterate = sched._iterate

        def iterate_then_gate(name, lane):
            iterate(name, lane)
            if self.want.is_set() and not self.go.is_set():
                self.parked.set()
                assert self.go.wait(60), "the test never let the loop go on"

        sched._iterate = iterate_then_gate


def _rows_of(be, call):
    return [be.cache.block_table(s.seq_id, be.max_blocks_per_seq)
            for s in call.members]


@pytest.mark.parametrize("kind", KINDS)
def test_a_hot_swap_gives_every_live_sequence_a_new_row(models, kind):
    """The swap lands between two steps: both live sequences are
    re-prefilled on the new backend under new ``seq_id``s, with rows
    made from the new cache's allocation (two more rows built); the
    old cache has forgotten theirs, and the new backend's first call
    gets a new table of those rows (and their slots in the new cache)."""
    name = "batch_swap_" + kind
    sched, be1 = _scheduler(models, kind, name, Recording, buckets=(2,))
    gate = Gate(sched)
    built = _rows_built(name)
    with be1.lane.entry.dispatch_lock:
        reqs = [sched.submit(name, np.asarray(p, np.int32),
                             max_new_tokens=20)
                for p in ([1, 2, 3], [4, 5, 6, 7, 8])]
    _wait(lambda: len(be1.calls) >= 3, "no decode step ran")
    gate.want.set()
    assert gate.parked.wait(60)
    old = list(be1.lane.active)
    assert len(old) == 2 and _rows_built(name) - built == 2
    be2 = _backend(models, kind, name, Recording)
    be2.lane, be2.calls = be1.lane, []
    sched.swap(name, be2)
    gate.go.set()
    streams = [r.result(timeout=120) for r in reqs]
    sched.close()
    assert all(len(s) == 20 for s in streams)
    assert _rows_built(name) - built == 4
    assert _counter("generation_reprefills_total", name) == 2

    first = be2.calls[0]
    assert [s.req for s in first.members] == reqs
    for was, now in zip(old, first.members):
        assert now is not was and now.seq_id != was.seq_id
        assert now.table is not was.table
        assert now.backend_ref is be2
        with pytest.raises(MXNetError, match="unknown sequence"):
            be1.cache.block_table(was.seq_id, be1.max_blocks_per_seq)
    assert first.args[2] is not be1.calls[-1].args[2]
    np.testing.assert_array_equal(first.copies[2], first.rebuilt[2])
    if kind == "stateful":
        np.testing.assert_array_equal(first.slots_handed,
                                      first.slots_looked_up)
    assert be1.cache.stats()["used"] == be2.cache.stats()["used"] == 0
    assert be1.cache.stats()["state_slots_used"] == 0
    if kind == "dense":
        # served as without the swap (the same weights; a state that a
        # prefill scanned in chunks is the stepped one only to rounding)
        plain, _ = _scheduler(models, kind, name + "_plain", buckets=(2,))
        for req, stream in zip(reqs, streams):
            assert plain.generate(name + "_plain", req.prompt,
                                  max_new_tokens=20) == stream
        plain.close()


def test_a_state_hazard_resume_gives_fresh_rows_and_slots(models):
    """A step fails behind a queued one: both live sequences are
    re-prefilled (new ``seq_id``, new row, a slot read anew), two more
    rows are built, and the call after the resume is handed a new table
    whose slots are the new sequences'."""
    name = "batch_hazard_resume"
    sched, be = _scheduler(models, "stateful", name, Recording,
                           buckets=(2,))
    built = _rows_built(name)
    with chaos.inject("serving.decode", "raise", match=":fetch",
                      limit=1) as inj:
        with be.lane.entry.dispatch_lock:
            reqs = [sched.submit(name, np.asarray(p, np.int32),
                                 max_new_tokens=12)
                    for p in ([1, 2, 3], [4, 5, 6, 7, 8])]
        streams = [r.result(timeout=120) for r in reqs]
    sched.close()
    assert inj.fires == 1
    resumed = om.REGISTRY.get("generation_state_hazard_total").labels(
        name, "resumed").value
    assert resumed == 2 and _rows_built(name) - built == 4
    # the failed call never returned, so it is not among the recorded:
    # the first recorded call is the one after the resume
    first = be.calls[0]
    assert [s.seq_id for s in first.members] == [name + "/3", name + "/4"]
    np.testing.assert_array_equal(first.copies[2], first.rebuilt[2])
    np.testing.assert_array_equal(first.slots_handed, first.slots_looked_up)
    for call in be.calls[1:]:
        if call.members == first.members:
            assert call.args[2] is first.args[2]
    assert [len(s) for s in streams] == [12, 12]
    assert be.cache.stats()["used"] == 0
    assert be.cache.stats()["state_slots_used"] == 0


@pytest.mark.parametrize("kind", KINDS)
def test_free_forgets_row_and_slot_and_an_unknown_sequence_raises(models,
                                                                  kind):
    be = _backend(models, kind, "batch_free_" + kind)
    cache, width = be.cache, be.max_blocks_per_seq
    with pytest.raises(MXNetError, match="unknown sequence"):
        cache.block_table("never", width)
    cache.allocate("s", 10)
    row = cache.block_table("s", width)
    assert row.dtype == np.int32 and row.shape == (width,)
    assert cache.block_table("s", width) is not row     # new every call
    if kind == "stateful":
        slots = cache.state_slots(row[None], [4])
        assert 0 <= slots[0] < cache.num_slots
        assert cache.state_slots(row[None], [0])[0] == cache.num_slots
    assert cache.free("s") == row[:3].tolist()
    with pytest.raises(MXNetError, match="unknown sequence"):
        cache.block_table("s", width)
    if kind == "stateful":
        with pytest.raises(MXNetError, match="is free"):
            cache.state_slots(row[None], [4])
        assert cache.stats()["state_slots_used"] == 0


@pytest.mark.parametrize("kind", KINDS)
def test_the_backend_knows_a_table_again_by_identity(models, kind):
    """Driven by hand: the same table object is booked and sent once,
    a copy of it is another table (sent again, compared by value with
    the queued step's, and equal), and a table of other blocks drops
    the queued step."""
    name = "batch_identity_" + kind
    be = _backend(models, kind, name)
    h2d = om.REGISTRY.get("generation_host_to_device_bytes_total").labels(
        name, "decode")
    be.cache.allocate("s", 16)
    padded = np.zeros(8, np.int32)
    padded[:3] = [1, 2, 3]
    _, k, v, _, *state = be.prefill(padded, 3)
    be.cache.write_prefill("s", k, v, 3, *state)
    table = be.cache.block_table("s", be.max_blocks_per_seq)[None]
    vectors = 3 * 4 + 2 * 4         # three [1] vectors, the write's two
    extra = 4 if kind == "stateful" else 0          # the slot
    be.run_ahead = True
    be.decode(np.array([5], np.int32), np.array([3], np.int32), table,
              np.array([4], np.int32))
    first = h2d.value
    # its own step and the queued one; the table and the slot once
    assert first == table.nbytes + extra + 2 * vectors - 4
    kept = be._table
    assert kept.host is table
    be.decode(be.greedy_ids, np.array([4], np.int32), table,
              np.array([5], np.int32))
    assert be._table is kept
    assert _counter("generation_decode_ahead_used_total", name) == 1
    # answered by the queue, and the step after it queued: ids from the
    # device, two fresh vectors and the write's two
    assert h2d.value - first == 4 * 4
    again = table.copy()
    be.decode(be.greedy_ids, np.array([5], np.int32), again,
              np.array([6], np.int32))
    assert be._table is not kept and be._table.host is again
    assert _counter("generation_decode_ahead_used_total", name) == 2
    be.run_ahead = False
    other = again.copy()
    other[0, -1] = 7                # a block no position reads
    be.decode(be.greedy_ids, np.array([6], np.int32), other,
              np.array([7], np.int32))
    assert _counter("generation_decode_ahead_dropped_total", name) == 1
    assert be._ahead is None
