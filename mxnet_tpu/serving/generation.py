"""Autoregressive generation lane: prefill/decode split + paged KV cache.

The :mod:`~mxnet_tpu.serving.scheduler` batches *fixed-shape* forward
passes — one dispatch answers one request.  Token generation inverts
the economics: a request is answered over hundreds of dispatches, and
the batch composition changes every step as sequences finish.  This
module is the serving tier's second dispatch discipline, the
Orca/vLLM model (Yu et al., OSDI '22; Kwon et al., SOSP '23) the
scheduler was already styled after:

- **Prefill/decode split.**  Each admitted request runs ONE prefill
  dispatch (the whole prompt, padded to a prompt-length bucket from
  ``MXNET_TPU_GEN_PREFILL_BUCKETS``), which fills its KV-cache pages
  and yields the first token.  After that it joins the shared *decode*
  batch: one token per sequence per step, padded to a batch bucket from
  ``MXNET_TPU_GEN_DECODE_BUCKETS``.  Both bucket ladders are shape keys
  into the backend's jit cache, so steady state recompiles **zero**
  times (``generation_compiles_total`` flat after :meth:`warmup` — the
  same tested contract as the classifier lane).
- **Iteration-level admission.**  The generation loop re-packs the
  decode batch EVERY step: a request submitted mid-generation is
  prefilled and joins the *next* decode step as finished sequences
  retire — nothing waits for the batch to drain
  (``generation_decode_occupancy`` and per-step row stats are the
  tested evidence).
- **Paged KV state.**  K/V lives in the backend's
  :class:`~mxnet_tpu.ops.kv_cache.PagedKVCache`; exhaustion sheds the
  new request with the typed 429
  :class:`~mxnet_tpu.ops.kv_cache.CacheExhaustedError` through the
  stock admission accounting.  Weights and pools stay on the device
  between dispatches: a call hands over token ids, positions and
  lengths and gets logits back
  (``generation_host_to_device_bytes_total`` /
  ``generation_device_to_host_bytes_total`` are the evidence).  **The
  decode batch's block table is kept, not rebuilt**: a sequence's
  padded row is made once, when it joins the batch (its whole horizon
  is reserved then, so the row cannot change), the lane puts the live
  rows together only when the batch gained or lost a sequence, and
  between such steps a decode call is handed the very array the last
  one got, which the backend knows again by identity: the table
  crosses to the device when it changes, not with every step
  (``generation_block_table_rows_built_total``).  The
  pool write follows the dispatch that produced its K/V (a decode
  step's inside :meth:`LMBackend.decode`, right behind the program) and
  targets only the calling sequences' own reserved slots, so a
  chaos-dropped or retried step can never corrupt another sequence's
  blocks; a write that fails after the pool was donated fails the
  lane's live sequences on a rebuilt, zeroed pool.
- **A full decode batch runs one step ahead.**  The decode program
  makes the greedy choice itself (``int32[B]`` beside the logits), so
  the only thing step n+1 needs from step n is already on the device:
  where the loop can see that no request could be admitted before step
  n+1 is answered (the batch full, no row at its last token, nothing
  shutting down: :meth:`GenerationScheduler._may_run_ahead`) the
  backend queues step n+1 before it waits for step n's logits, and the
  device computes while the host copies 3 MB of logits, pushes tokens
  and builds the next call.  Every logit still reaches the host and
  every served token is the ``argmax`` of them; a step that a request
  would have to wait behind is never queued, so first tokens wait no
  longer (``generation_decode_ahead_used_total`` /
  ``generation_decode_ahead_dropped_total``).
- **Recurrent state beside keys and values.**  A model whose
  definition has a ``state`` (layers that keep a fixed-size state per
  sequence: :class:`~mxnet_tpu.ops.kv_cache.StateRows`) gets a state
  slot with its blocks; its prefill writes the slot once and its decode
  program updates the state pool where it lies.  A recurrent update is
  not idempotent the way a key row's write is, so a slot keeps two
  versions by the parity of the position (:meth:`LMBackend.decode` has
  the account): a step that is dispatched again, or queued ahead and
  dropped, leaves every state as if each position had been consumed
  once, and the run-ahead above stays on.  The one case two versions do
  not cover, a step that fails *after* the step behind it was queued,
  is raised as :class:`RecurrentStateHazard`, and the loop re-prefills
  its live sequences (prompt + tokens so far) instead of retrying in
  place (``generation_state_hazard_total``).
- **Cache is backend state.**  ``ModelRegistry.swap`` replaces backend
  and cache together (the registry machinery is untouched); the loop
  notices the swap under ``dispatch_lock`` and transparently
  re-prefills live sequences on the new backend
  (``generation_reprefills_total``) — stale pages never mix with new
  weights, and hot-swap/brownout/rollback keep working.

Chaos sites: ``serving.decode`` fires inside the decode window before
the device call (name ``<model>:<bucket>``, retried
``MXNET_TPU_SERVING_RETRIES`` times) and, for a model with recurrent
state, once more inside :meth:`LMBackend.decode` behind the dispatch
(name ``<model>:fetch``: a step that fails once it, and maybe the step
after it, is on the device); ``serving.kv_alloc`` fires in the
allocator.  Prefill dispatches visit the existing ``serving.dispatch``
site (name ``<model>:prefill:<bucket>``).

Streaming: each :class:`GenerationRequest` is one ordered record of
its tokens and a count of how many are *released* to its reader: a
first token at once, a decode step's tokens once the loop's next device
call is on its way, so the readers run beside the device.
:meth:`GenerationRequest.tokens` yields them as they are released (a
reader in process); the front end's stream writer, one thread for all
of ``/v1/generate``'s chunked responses, registers a wake-up instead
(:meth:`GenerationRequest.stream_to`) and is woken once a delivery,
whatever the number of rows.
:meth:`GenerationRequest.cancel` (client disconnect) retires the
sequence and frees its blocks at the next iteration.
"""

from __future__ import annotations

import collections as _collections
import os
import sys as _sys
import threading
import time
import weakref as _weakref

import numpy as _np

from .. import chaos
from .. import compile_cache as _compile_cache
from ..base import MXNetError
from ..observability import memory as _memory
from ..observability import metrics as _metrics
from ..observability import tracing as _tracing
from ..observability.events import emit as _emit_event
from ..ops.kv_cache import (CacheExhaustedError, CachePoolLostError,
                            PagedKVCache)
from . import admission as _admission
from . import tenancy as _tenancy
from .registry import Backend, ModelRegistry
from .scheduler import default_retries

__all__ = ["GenerationRequest", "GenerationScheduler", "LMBackend",
           "RecurrentStateHazard", "default_decode_buckets",
           "default_prefill_buckets", "default_max_new_tokens"]


def default_prefill_buckets():
    """``MXNET_TPU_GEN_PREFILL_BUCKETS``: prompt-length pad targets."""
    raw = os.environ.get("MXNET_TPU_GEN_PREFILL_BUCKETS", "8,16,32,64")
    try:
        buckets = sorted({int(b) for b in raw.split(",") if b.strip()})
    except ValueError:
        buckets = [8, 16, 32, 64]
    return [b for b in buckets if b > 0] or [8]


def default_decode_buckets():
    """``MXNET_TPU_GEN_DECODE_BUCKETS``: decode batch pad targets."""
    raw = os.environ.get("MXNET_TPU_GEN_DECODE_BUCKETS", "1,2,4,8")
    try:
        buckets = sorted({int(b) for b in raw.split(",") if b.strip()})
    except ValueError:
        buckets = [1, 2, 4, 8]
    return [b for b in buckets if b > 0] or [1]


def default_max_new_tokens():
    """``MXNET_TPU_GEN_MAX_TOKENS``: per-request generation cap."""
    try:
        return int(os.environ.get("MXNET_TPU_GEN_MAX_TOKENS", "32"))
    except ValueError:
        return 32


# what crosses between host and device per generation call: the
# residency contract's witness.  A decode call reads a few KB in and
# ``B x V x 4`` out; a numpy array slipping back into a call (weights,
# a pool) shows here at once.
_M_H2D = _metrics.counter(
    "generation_host_to_device_bytes_total",
    "Host bytes handed to the device by generation calls (token ids, "
    "positions, block tables, lengths, cache slot indices; any numpy "
    "array among weights or pools), by model and phase",
    ["model", "phase"])
_M_D2H = _metrics.counter(
    "generation_device_to_host_bytes_total",
    "Device bytes generation calls copied back to the host (logits), "
    "by model and phase", ["model", "phase"])


_M_AHEAD_USED = _metrics.counter(
    "generation_decode_ahead_used_total",
    "Decode calls answered by a step that was queued behind the one "
    "before it (of generation_decode_steps_total), by model", ["model"])
_M_AHEAD_DROPPED = _metrics.counter(
    "generation_decode_ahead_dropped_total",
    "Queued decode steps thrown away: the next call asked for another "
    "step, or a fault, kill or swap came first, by model", ["model"])


_M_STATE_MOVED = _metrics.counter(
    "generation_state_bytes_total",
    "Bytes of recurrent state decode steps read and wrote (every live "
    "row's state once each way a step), by model", ["model"])
_M_STATE_HAZARD = _metrics.counter(
    "generation_state_hazard_total",
    "Live sequences re-prefilled (resumed) or failed because a decode "
    "step failed after the step behind it had overwritten the state it "
    "read, by model and outcome", ["model", "outcome"])


class RecurrentStateHazard(MXNetError):
    """A decode step of a model with recurrent state failed after the
    step behind it was queued: the queued step has overwritten the
    version of the state this step read, so it cannot be run again in
    place.  The live sequences have to be re-prefilled or failed."""


def _unheld_count():
    """What ``sys.getrefcount`` says of an array that a list, the loop
    over that list and the call itself refer to and nothing else: read
    from the interpreter, not assumed (:meth:`LMBackend._host_logits`
    asks it in a loop of the same form)."""
    for array in [_np.empty(0)]:
        return _sys.getrefcount(array)


_UNHELD = _unheld_count()


def _host_nbytes(arrays):
    """Bytes of the numpy arrays among ``arrays``: what a jitted call
    given them has to stage on the device."""
    return sum(a.nbytes for a in arrays if isinstance(a, _np.ndarray))


class GenerationRequest(object):
    """One admitted generation request: a token stream plus a future.

    The generation loop appends token ids to ``generated`` as decode
    steps complete and *releases* them (``released``: how many of them
    the stream's reader may have) beside its next device call.  One
    ordered record, read through a cursor: :meth:`tokens` yields the
    released tokens live and :meth:`result` blocks for the full list;
    a reader that serves many requests from one thread (the front
    end's stream writer) registers its wake-up with :meth:`stream_to`
    and reads ``generated[:released]`` itself.  ``trace`` is the
    submitter's wire token, the request's identity in the merged trace.
    """

    __slots__ = ("model", "prompt", "max_new_tokens", "eos_id", "deadline",
                 "tenant", "t_admit", "trace", "generated", "error",
                 "finish_reason", "latency_s", "first_token_s", "seq_id",
                 "_released", "_done", "_cond", "_wake", "_cancelled",
                 "_h_tenant", "_h_tokens")

    def __init__(self, model, prompt, max_new_tokens, eos_id, deadline,
                 tenant=_tenancy.DEFAULT_TENANT):
        self.model = model
        self.prompt = prompt
        self.max_new_tokens = max_new_tokens
        self.eos_id = eos_id
        self.deadline = deadline
        self.tenant = tenant
        self.t_admit = time.monotonic()
        self.trace = None
        self.generated = []
        self.error = None
        self.finish_reason = None
        self.latency_s = None
        self.first_token_s = None
        self.seq_id = None
        self._released = 0
        self._done = False
        self._cond = threading.Condition(threading.Lock())
        self._wake = None
        self._cancelled = False
        # pre-resolved per-tenant counter handles (attached at submit,
        # None with metrics disabled) — the decode loop never resolves
        # labels
        self._h_tenant = None
        self._h_tokens = None

    @property
    def done(self):
        return self._done

    @property
    def released(self):
        """How many of ``generated`` the stream's reader may have.  Read
        ``done`` first: once that is set this is final."""
        return self._released

    @property
    def cancelled(self):
        return self._cancelled

    def cancel(self):
        """Client went away: the loop retires the sequence and frees its
        cache blocks at the next iteration.  Safe from any thread."""
        self._cancelled = True

    # -- loop side ---------------------------------------------------

    def _push(self, token):
        """Record a token; :meth:`_deliver` releases it to the stream."""
        if self.first_token_s is None:
            self.first_token_s = time.monotonic() - self.t_admit
        self.generated.append(int(token))

    def _deliver(self):
        """Release the recorded tokens to the stream's reader.  The loop
        holds a decode step's tokens (one a request) until its next
        device call is on the way, so the readers run beside the device
        and not between two of its calls.  A reader inside
        :meth:`tokens` is woken here; for one that registered a wake-up
        (:meth:`stream_to`) that is returned instead, for the caller to
        call once for all its rows.  ``None`` where nothing is new."""
        n = len(self.generated)
        if n == self._released:
            return None
        if self._wake is None:
            with self._cond:
                self._released = n
                self._cond.notify_all()
                return self._wake       # registered meanwhile: wake it
        self._released = n
        return self._wake

    def _finish(self, reason, error=None, owed=None):
        """End the request.  A reader in process is woken at once; so is
        a registered one, unless the caller takes its wake-up into
        ``owed`` (a set) to call it beside its next device call."""
        with self._cond:
            if self._done:           # idempotent: kill vs loop race
                return
            self.error = error
            self.finish_reason = reason
            self.latency_s = time.monotonic() - self.t_admit
            self._released = len(self.generated)
            self._done = True
            self._cond.notify_all()
            wake = self._wake
        if wake is None:
            return
        if owed is None:
            wake()
        else:
            owed.add(wake)

    def _fail(self, error):
        self._finish("error", error)

    # -- client side -------------------------------------------------

    def wait(self, count, timeout=30.0):
        """Block until ``count`` tokens are released or the request is
        over; returns ``(released, done)`` as of then."""
        with self._cond:
            if not self._cond.wait_for(
                    lambda: self._released >= count or self._done, timeout):
                raise MXNetError(
                    "generation on model %r: no token for %.1fs"
                    % (self.model, timeout))
            return self._released, self._done

    def tokens(self, timeout=30.0):
        """Yield generated token ids as they are released; raises the
        typed serving error if generation failed."""
        sent = 0
        while True:
            released, done = self.wait(sent + 1, timeout)
            yield from self.generated[sent:released]
            sent = released
            if done:
                if self.error is not None:
                    raise self.error
                return

    def stream_to(self, wake):
        """Register the stream's reader: a thread that serves many
        requests and reads ``generated[:released]`` itself.  ``wake()``
        is called, from the loop's thread, once for every delivery that
        released a token of any of its requests, and for this request's
        end (beside the loop's next device call, or at once where none
        follows); it must be cheap and must not block (an
        ``Event.set``).
        ``None`` takes the registration back.  A request has one reader:
        :meth:`tokens` is not woken per token while a wake-up is
        registered."""
        with self._cond:
            self._wake = wake

    def result(self, timeout=30.0):
        """Block until generation finishes; returns the generated ids."""
        with self._cond:
            if not self._cond.wait_for(lambda: self._done, timeout):
                raise MXNetError(
                    "generation on model %r timed out after %.1fs"
                    % (self.model, timeout))
        if self.error is not None:
            raise self.error
        return list(self.generated)


def with_greedy_ids(decode):
    """An :class:`~mxnet_tpu.models.lm.LMDefinition`'s ``decode`` with
    the greedy choice made beside it, from the very logits the host
    gets (the first maximum, as ``numpy.argmax``): the program
    :meth:`LMBackend.decode` runs, ``(logits, ids int32 [B], k_rows,
    v_rows, counts)`` (and the state pools, where the model has
    them)."""
    import jax.numpy as jnp

    def program(params, *args):
        logits, *rest = decode(params, *args)
        return (logits, jnp.argmax(logits, axis=-1).astype(jnp.int32),
                *rest)

    return program


def _program_label(key):
    """A jit-cache key as a start-up scope's ``program``:
    ``("prefill", 1024)`` is ``prefill:1024``."""
    return "%s:%s" % (key[0], "x".join(str(n) for n in key[1:]))


class _Table(_collections.namedtuple("_Table", "host device slots live")):
    """A decode batch's block table as :meth:`LMBackend.decode` was
    handed it (``host``, known again by identity), its copy on the
    device and, for a model with recurrent state, the rows' state slots
    there (``live`` of them a sequence's): what is derived from a table
    once and serves every step that is handed the same one."""

    __slots__ = ()


class _Step(_collections.namedtuple(
        "_Step", "fed logits ids k v counts cold")):
    """One decode step on the device's queue: what it was fed (numpy,
    the caller's own arrays, kept by reference and never written; a
    queued step's tokens are known once the step before it is read) and
    its outputs, device arrays whose copies to the host are on their
    way."""

    __slots__ = ()


class LMBackend(Backend):
    """Generative serving backend: a model's params + paged KV cache
    + shape-keyed jit caches for prefill and decode.

    The model is whatever ``definition`` (an
    :class:`~mxnet_tpu.models.lm.LMDefinition`) says: the functions the
    prefill and decode programs are built from, and the row its cache
    keeps per token.  Without one, ``cfg`` is a
    :func:`~mxnet_tpu.models.transformer.lm_config` and the model is
    that transformer.

    Registers through the stock :class:`~.registry.ModelRegistry` (it IS
    a :class:`~.registry.Backend`), so ``swap``'s ``dispatch_lock``
    atomicity and signature checks apply unchanged — and because the
    cache lives HERE, a hot swap replaces weights and KV state as one
    unit.

    **Everything a call reads lives on the device between calls.**  The
    constructor places the weight tree once (``self.params`` stays a
    dict under the same names, its leaves device arrays) and the cache
    keeps its pools there; :meth:`prefill` and :meth:`decode` hand over
    token ids, positions, block tables and lengths and copy back only
    logits (and, from a decode step, the ``int32[B]`` of their greedy
    ids).  The K/V they return are device arrays; a prefill's are for
    :meth:`~mxnet_tpu.ops.kv_cache.PagedKVCache.write_prefill`, a decode
    step's are written by :meth:`decode` itself.  During
    a hot swap's brownout two backends are alive, so two resident sets
    (weights + pool each) are on the device at once: 2 x 3.76 GB =
    7.5 GB for GPT-2 medium with a 680-block pool, of 16 GB.

    **A decode step may run one step ahead.**  Where its caller sets
    ``run_ahead`` for a call (the generation loop does, per call, from
    what it sees of its lane: :meth:`GenerationScheduler._may_run_ahead`)
    :meth:`decode` queues the step after this one, fed by this step's
    greedy ids as the device holds them, before it waits for this
    step's logits; the next call is answered by the queued step if it
    asks for exactly that step, and throws it away if not.  Nothing is
    skipped or approximated: every step's logits are computed and
    copied back whole, behind the next step's compute and not in front
    of it.  ``generation_decode_ahead_used_total`` /
    ``generation_decode_ahead_dropped_total`` count both outcomes.

    **A model with recurrent state** (``definition.state``) has a slot
    of ``state_slots`` a live sequence beside its blocks, in two
    versions: the step at position ``p`` reads version ``p % 2`` and
    writes ``(p + 1) % 2``, inside the decode program, which is handed
    the state pool donated and updates it where it lies.  So a step
    that is dispatched again (a retry) finds what it read untouched and
    writes the same values again, and a queued step that is dropped has
    read what the step before it wrote and overwritten only what that
    step had read, which no one needs again: every state is as if each
    position had been consumed once, and run-ahead stays on.  What two
    versions cannot cover is a step that fails after the step behind it
    was queued: :meth:`decode` raises :class:`RecurrentStateHazard`
    then, and the caller re-prefills (the generation loop does).

    ``int8_head=True`` opts into the
    :func:`~mxnet_tpu.contrib.quantization.quantize_weight_int8` vocab
    head for decode logits (storage/bandwidth win on the model's
    largest matmul); prefill keeps the fp32 head so the first token
    stays on the parity contract.
    """

    @_compile_cache.scope("backend.build")
    def __init__(self, params, cfg=None, block_size=None, num_blocks=None,
                 int8_head=False, model="lm", definition=None,
                 state_slots=None):
        import jax

        if definition is None:
            from ..models import transformer

            definition = transformer.lm_definition(cfg, int8_head)
        elif int8_head:
            raise MXNetError("int8_head is the default transformer's; a "
                             "definition brings its own `prepare`")
        self.definition = definition
        self.model = model
        self.cfg = dict(definition.cfg)
        self.int8_head = bool(int8_head)
        self.params = jax.device_put(
            definition.prepare(params) if definition.prepare
            else dict(params))
        self.input_shapes = {"data": (self.cfg["seq_len"],)}
        self.cache = PagedKVCache(
            num_layers=definition.cache_layers or self.cfg["num_layers"],
            row=definition.cache_row, block_size=block_size,
            num_blocks=num_blocks, model=model, state=definition.state,
            state_slots=state_slots, groups=definition.cache_groups,
            max_tokens=self.cfg["seq_len"])
        # every sequence gets a fixed-width block table: the decode jit
        # signature depends only on the batch bucket, never on how long
        # any sequence has run — the zero-recompile contract.  (Of a
        # model with layer groups the row holds every group's table,
        # side by side: ``num_blocks`` is then a number a group.)
        self.max_blocks_per_seq = self.cache.table_width
        # the windows of the model's sliding-window layer groups
        self.windows = tuple(window for _, window in
                             definition.cache_groups or () if window)
        self._jits = {}
        self._jit_lock = threading.Lock()
        self._decode_program = with_greedy_ids(definition.decode)
        # what the caller wants done while the device runs a prefill or
        # decode call: called once the program is on its way and before
        # the call waits for the logits (the generation loop hands the
        # last step's tokens to their streams here)
        self.beside_device = None
        # set by the caller for one decode call: queue the step after it
        # too (see the class docstring); the queued step, if any; and
        # the greedy ids of the step the last decode call answered,
        # int32 [B] on the host: what the caller serves and feeds on
        self.run_ahead = False
        self._ahead = None
        self.greedy_ids = None
        # the block table the last decode call was handed, with what
        # was derived from it (:meth:`_on_device`)
        self._table = None
        # the host arrays the last decode steps' logits were handed out
        # in (:meth:`_host_logits`), at most two
        self._handed = []
        self._ahead_used = _M_AHEAD_USED.labels(model)
        self._ahead_dropped = _M_AHEAD_DROPPED.labels(model)
        self._state_moved = _M_STATE_MOVED.labels(model)
        self._moved = {(phase, way): fam.labels(model, phase)
                       for phase in ("prefill", "decode")
                       for way, fam in (("h2d", _M_H2D), ("d2h", _M_D2H))}
        # book the weight tree into the memory ledger (serving-lane
        # analogue of the trainer's params seam); keyed by backend so a
        # hot-swap replaces the old backend's row when it is collected
        _memory.tag_tree("params", id(self), self.params)
        _weakref.finalize(self, _memory.untag, "params", id(self))

    def _jit(self, key, program, donate=()):
        """Shape-keyed jit cache of the definition's ``program``
        (``donate``: the arguments it may write where they lie);
        returns (fn, cold).  A cold ``fn`` handed out outside
        :meth:`GenerationScheduler.warmup` (no start-up scope is open)
        runs, this once, under the start-up scope ``serve.cold``: a
        compile that serving paid for is booked as one."""
        with self._jit_lock:
            fn = self._jits.get(key)
            cold = fn is None
            if cold:
                import jax

                fn = jax.jit(program, donate_argnums=donate)
                self._jits[key] = fn
                if not _compile_cache.is_open():
                    fn = _compile_cache.scope(
                        "serve.cold", _program_label(key))(fn)
        return fn, cold

    def moved(self, phase, h2d=0, d2h=0):
        """Book bytes a ``phase`` (``prefill`` / ``decode``) call moved
        between host and device."""
        self._moved[phase, "h2d"].inc(h2d)
        self._moved[phase, "d2h"].inc(d2h)

    # -- Backend protocol (full forward; also the naive baseline) ----

    def infer(self, batch):
        """Full-sequence forward (no cache) — the classifier-lane
        protocol, and the bench's naive re-prefill baseline."""
        tokens = _np.asarray(batch["data"], dtype=_np.int32)
        fn, cold = self._jit(("infer",) + tokens.shape,
                             self.definition.forward)
        return [_np.asarray(fn(self.params, tokens))], cold

    def _fetch(self, phase, logits, counts, ids=None):
        """A dispatched call's logits as a host copy (in ordinary
        memory: np.asarray of a device array is a view of the runtime's
        transfer buffer), with a decode step's greedy ids; what the
        program counted rides back beside them and is booked, and so
        are the bytes copied.  The caller's ``beside_device`` runs
        first: the device is at work by now.  A decode step's fetch is
        three spans (``decode.deliver``, ``decode.wait``,
        ``decode.copy``); a prefill's is one, its caller's."""
        if phase != "decode":
            if self.beside_device is not None:
                self.beside_device()
            return self._copy(phase, logits, counts, ids)
        with _tracing.span("decode.deliver", cat="serving",
                           model=self.model):
            if self.beside_device is not None:
                self.beside_device()
        if _tracing.tracing_enabled():
            # only a recorded step is asked when its outputs exist apart
            # from their copy: unrecorded, the copy below waits for both
            import jax

            with _tracing.span("decode.wait", cat="serving",
                               model=self.model):
                jax.block_until_ready(ids)
        with _tracing.span("decode.copy", cat="serving", model=self.model):
            return self._copy(phase, logits, counts, ids)

    def _copy(self, phase, logits, counts, ids):
        """:meth:`_fetch`'s copies into ordinary memory, booked."""
        logits = (self._host_logits(logits) if phase == "decode"
                  else _np.array(logits))
        d2h = logits.nbytes
        if ids is not None:
            ids = _np.array(ids)
            d2h += ids.nbytes
        if counts is not None:
            counts = _np.asarray(counts)
            d2h += counts.nbytes
            self.definition.book(self.model, counts)
        self.moved(phase, d2h=d2h)
        return logits, ids

    def _host_logits(self, logits):
        """A decode step's ``logits`` in ordinary memory: in an array an
        earlier step's were handed out in, if whoever got that array
        has let it go, else in a new one.  A step's logits are
        ``rows x vocabulary x 4`` bytes, and an array of megabytes made
        and freed every step is mapped, faulted in page by page and
        unmapped again in some steps and taken from the allocator's
        heap in others: 33 MB (32 rows of 261,120) cost 3-4 ms or 30-42
        ms a step on the v5e's host, 3.6 ms into an array that is kept
        (PERF.md section 6, PR 48).  A caller that keeps the array, or
        a view of it, keeps it for good: only an array that nothing but
        this backend refers to is written again."""
        src = _np.asarray(logits)
        for out in self._handed:
            # referred to by the list, by this loop and by the call that
            # counts: nobody holds it or a view of it (a view's ``base``)
            if (_sys.getrefcount(out) == _UNHELD and out.shape == src.shape
                    and out.dtype == src.dtype):
                _np.copyto(out, src)
                return out
        out = _np.array(src)
        self._handed = self._handed[-1:] + [out]
        return out

    @staticmethod
    def _copy_back(*outputs):
        """Start the outputs' copies to the host: they move while the
        host (and, behind a decode step, the device) does what is next."""
        for out in outputs:
            if out is not None:
                out.copy_to_host_async()

    # -- generation entry points -------------------------------------

    def prefill(self, tokens, length):
        """One prompt (``tokens`` int32 ``[T_bucket]`` padded, ``length``
        real) → ``(last_logits [V], k [L, T_bucket, row width], v,
        cold)``; ``v`` is ``None`` where the cache's row has one pool.
        The logits are a host copy; ``k``/``v`` are device arrays over
        the whole bucket, for ``cache.write_prefill(seq, k, v, length)``
        (which drops the pad positions); ``cold`` reports the jit-cache
        miss for compile accounting.  A model with recurrent state
        returns a fifth value, the prompt's state rows at ``length``
        (device arrays), for the same ``write_prefill``."""
        tokens = _np.asarray(tokens, dtype=_np.int32)
        args = (tokens, _np.asarray(length, dtype=_np.int32))
        fn, cold = self._jit(("prefill",) + tokens.shape,
                             self.definition.prefill)
        with _tracing.span("prefill.dispatch", cat="serving",
                           model=self.model):
            logits, k, v, counts, *state = fn(self.params, *args)
        self._copy_back(counts)
        self.moved("prefill", h2d=_host_nbytes(
            (*self.params.values(), *args)))
        with _tracing.span("prefill.fetch", cat="serving",
                           model=self.model):
            logits = self._fetch("prefill", logits, counts)[0]
        return (logits, k, v, cold, *state)

    def _on_device(self, block_tables, positions):
        """The :class:`_Table` of ``block_tables``: the one kept from
        the last call if this is the very array that call was handed
        (what was handed over is never written again, so the same
        object is the same table), else made now: the table crosses to
        the device, and the rows' state slots are looked up (a model
        with recurrent state), once a table and not once a step."""
        table = self._table
        if table is None or table.host is not block_tables:
            import jax

            slots, live = None, 0
            if self.cache.state:
                slots = self.cache.state_slots(block_tables, positions)
                live = int((slots < self.cache.num_slots).sum())
            self.moved("decode", h2d=_host_nbytes((block_tables, slots)))
            table = self._table = _Table(
                block_tables, *jax.device_put((block_tables, slots)), live)
        return table

    def _dispatch_decode(self, tokens, positions, table, context_lens):
        """Put one decode step on the device's queue and return it: the
        program, the copies of what it gives back, and right behind it
        the write of its K/V rows into the pool, so that whatever is
        dispatched next reads a pool that holds this step.  ``tokens``
        is numpy, or the device's ids of the step before; ``table`` the
        batch's :class:`_Table` (the program reads its device copy, the
        pool write its host copy).  A model with recurrent state is
        handed the state pool donated, with the rows' state slots, and
        the cache is re-bound to what it gives back."""
        args = (tokens, positions, *self.cache.program_pools(),
                table.device, context_lens)
        if table.slots is None:
            fn, cold = self._jit(("decode", len(positions)),
                                 self._decode_program)
            logits, ids, k, v, counts = fn(self.params, *args)
        else:
            fn, cold = self._jit(("decode", len(positions)),
                                 self._decode_program, donate=(7,))
            try:
                logits, ids, k, v, counts, pools = fn(
                    self.params, *args, self.cache.state_pools,
                    table.slots)
            except Exception as exc:
                lost = self.cache.state_lost(exc)
                if lost is None:
                    raise
                raise lost from exc
            self.cache.swap_state(pools)
            self._state_moved.inc(
                2 * table.live * self.cache.state.bytes)
        self._copy_back(logits, ids, counts)
        self.moved("decode", h2d=_host_nbytes(
            (*self.params.values(), *args))
            + self.cache.write_tokens(table.host, positions, k, v))
        return _Step((tokens, positions, table.host, context_lens),
                     logits, ids, k, v, counts, cold)

    def drop_ahead(self):
        """Throw the queued step away, if there is one (what it wrote is
        harmless: see :meth:`decode`).  For the thread that calls
        :meth:`decode`."""
        if self._ahead is not None:
            self._ahead = None
            self._ahead_dropped.inc()

    def decode(self, tokens, positions, block_tables, context_lens):
        """One decode step over a padded batch.  Returns ``(logits
        [B, V], k_step [L, B, row width], v_step, cold)``: the logits a
        host copy, ``k_step``/``v_step`` device arrays.  The step's
        greedy ids (``argmax`` of those logits, made on the device) are
        left in ``self.greedy_ids``, int32 ``[B]`` on the host.  The
        logits' array is the caller's for as long as it keeps it or a
        view of it; one it has let go may be the array a later call's
        logits come in (:meth:`_host_logits`).

        The program reads the pool as of before the step; **the write
        of ``k_step``/``v_step`` follows it inside this call**, into the
        slots ``(block_tables[i, positions[i] // block_size],
        positions[i] % block_size)``; a row at position 0 is a pad row
        and writes nowhere.  A slot written again gets the same values,
        so a call may be repeated (a retry) and the caller need write
        nothing; a caller that still hands ``k_step``/``v_step`` to
        ``cache.write_tokens`` changes nothing.

        With ``self.run_ahead`` set, the step after this one is put on
        the device's queue before this step's logits are waited for:
        the same bucket and tables, ``tokens`` the device's own ids of
        this step, ``positions + 1``, ``context_lens + 1``, then its
        write.  The next call gets that step's results if its four
        arguments equal what was queued (the same ``block_tables``
        object is taken as equal, unread); any other call drops it and
        is dispatched afresh, as is any call after an error.  Equal
        arguments are not always the same batch: a row's successor may
        get its blocks, stand at its position + 1 and feed its token.
        So a caller whose batch gained or lost a row calls
        :meth:`drop_ahead` before it decodes (the generation loop does,
        with the new table array it makes then).  A dropped
        step's write is harmless: a row that goes on rewrites the slot
        with the same values; one that does not owns the slot until its
        blocks are freed, whoever gets them next writes a position
        before ``context_lens`` lets a step read it, and the device
        runs what it is handed in order.

        **Recurrent state** (a definition with ``state``) is updated by
        the program itself, in the donated state pool: row ``i`` reads
        version ``positions[i] % 2`` of its sequence's slot (found
        through its block table) and writes the other version.  A call
        that is repeated therefore reads what the first one read and
        writes the same values again, and a queued step that is dropped
        has overwritten only the version the step before it read.  Not
        so a call that fails once the step after it is queued: that
        step has overwritten what a repeat would read, and the error
        raised is :class:`RecurrentStateHazard`; the caller re-prefills
        its sequences or fails them.

        **The arguments are not written after the call.**  The queued
        step keeps references to them, and a jitted call on the CPU
        platform may read an aligned numpy argument where it lies: a
        caller makes new arrays for the next step and leaves these as
        they are.  In return the same ``block_tables`` object (an
        ``int32`` array) is the same table: its 4 bytes an entry are
        neither compared with the queued step's nor sent again; its
        copy on the device, and the rows' state slots, are kept with it
        until a call brings another array, and
        ``generation_host_to_device_bytes_total`` books the table only
        then.  A caller that builds a table per call gets what it got
        before: every table is sent, and compared by value.

        A subclass that overrides this method with these four
        arguments and calls it (the benchmark's wrapper does) sees one
        call a step, numpy arguments, and a numpy ``out[0]`` that
        belongs to those arguments."""
        fed = tuple(_np.asarray(a, dtype=_np.int32) for a in
                    (tokens, positions, block_tables, context_lens))
        step = self._ahead
        if step is not None and all(
                a is b or _np.array_equal(a, b)
                for a, b in zip(step.fed, fed)):
            table = self._on_device(fed[2], fed[1])
            self._ahead = None
            self._ahead_used.inc()
        else:
            with _tracing.span("decode.dispatch", cat="serving",
                               model=self.model, ahead=0):
                table = self._on_device(fed[2], fed[1])
                self._ahead = None
                if step is not None:
                    self._ahead_dropped.inc()
                step = self._dispatch_decode(fed[0], fed[1], table, fed[3])
        ahead = None
        try:
            if self.run_ahead:
                with _tracing.span("decode.dispatch", cat="serving",
                                   model=self.model, ahead=1):
                    ahead = self._dispatch_decode(
                        step.ids, fed[1] + 1, table, fed[3] + 1)
            if table.slots is not None:
                # the drill of a step that fails behind its dispatch
                chaos.visit("serving.decode", name="%s:fetch" % self.model)
            logits, ids = self._fetch("decode", step.logits, step.counts,
                                      step.ids)
        except Exception as exc:
            if ahead is not None:
                self._ahead_dropped.inc()
            if (table.slots is not None and self.run_ahead
                    and not isinstance(exc, CachePoolLostError)):
                raise RecurrentStateHazard(
                    "model %r: a decode step failed after the step "
                    "behind it was queued (%s: %s); the state it read "
                    "is overwritten" % (self.model, type(exc).__name__,
                                        exc)) from exc
            raise
        if ahead is not None:
            self._ahead = ahead._replace(fed=(ids,) + ahead.fed[1:])
        self.greedy_ids = ids
        return logits, step.k, step.v, step.cold

    def describe(self):
        d = Backend.describe(self)
        d.update({"generative": True, "int8_head": self.int8_head,
                  "kv_cache": self.cache.stats()})
        return d


class _Sequence(object):
    """One live generation: its request, cache identity, and progress.

    ``table`` is the sequence's padded block-table row, ``int32
    [max_blocks_per_seq]``, made once from the allocation it got when
    it joined the decode batch.  The whole horizon (prompt +
    ``max_new_tokens``) is reserved then and never grown, so the row
    holds until ``cache.free``, and with it the state slot the cache
    gave the sequence beside its blocks (found through the row's first
    block: ``PagedKVCache.state_slots``).  The row is never written: a
    re-prefilled sequence (hot swap, :class:`RecurrentStateHazard`) is
    a new ``_Sequence`` with a new ``seq_id``, new blocks, a new slot
    and a new row."""

    __slots__ = ("req", "seq_id", "length", "last_token", "backend_ref",
                 "new_tokens", "t_last_token", "table")

    def __init__(self, req, seq_id, backend_ref):
        self.req = req
        self.seq_id = seq_id
        self.backend_ref = backend_ref
        self.length = 0          # tokens with K/V in the cache
        self.last_token = 0      # input to the next decode step
        self.new_tokens = 0
        self.t_last_token = time.monotonic()
        self.table = backend_ref.cache.block_table(
            seq_id, backend_ref.max_blocks_per_seq)


class _GenLane(object):
    """Per-model waiting queue + live sequences + the generation thread
    + pre-resolved metric handles."""

    __slots__ = ("entry", "queue", "active", "owed", "thread", "steps",
                 "tokens", "rows", "slots", "max_step_rows", "seq_counter",
                 "tenant_handles", "seated", "tables",
                 "m_req", "m_prefill", "m_itl", "m_depth", "m_occ",
                 "m_active", "m_requests", "m_tokens", "m_steps",
                 "m_context", "m_window", "m_compiles", "m_errors",
                 "m_reprefills", "m_table_rows")

    def __init__(self, entry, weight_fn=None):
        self.entry = entry
        self.queue = _tenancy.FairQueue(weight_fn)
        self.tenant_handles = {}
        self.active = []
        # the decode batch's block table, ``int32 [bucket,
        # max_blocks_per_seq]``, and the sequences whose rows it holds,
        # in order: replaced together, by new objects, when ``active``
        # is no longer that list (``_decode_step``)
        self.seated = []
        self.tables = None
        # wake-ups of stream readers whose requests ended since the last
        # device call: called beside the next one (or when none follows)
        self.owed = set()
        self.thread = None
        self.steps = 0
        self.tokens = 0
        self.rows = 0
        self.slots = 0
        self.max_step_rows = 0
        self.seq_counter = 0


class GenerationScheduler(object):
    """Iteration-level generation scheduler for one serving replica.

    Mirrors :class:`~.scheduler.Scheduler`'s lifecycle (drain / close /
    kill, heartbeat, per-model lanes) but each lane runs the
    prefill/decode loop instead of one-shot dispatch windows.
    """

    def __init__(self, registry=None, metrics_registry=None, name="gen0",
                 tenant_policy=None):
        self.name = name
        self.registry = registry if registry is not None else ModelRegistry()
        self._reg = (metrics_registry if metrics_registry is not None
                     else _metrics.REGISTRY)
        self.tenants = (tenant_policy if tenant_policy is not None
                        else _tenancy.TenantPolicy())
        self.admission = _admission.AdmissionController(
            reject_counter=self._reg.counter(
                "serving_rejected_total", _admission.REJECTED_HELP,
                _admission.REJECTED_LABELS))
        self._fam = self._families(self._reg)
        self._cond = threading.Condition()
        self._lanes = {}
        self._stopping = False
        self._killed = False
        # membership identity (replication.ReplicaGroup): a generation
        # replica fences exactly like a classifier replica
        self._fenced_epoch = None
        self.epoch = 0
        self.last_beat = time.monotonic()

    @staticmethod
    def _families(reg):
        return {
            "req": reg.histogram(
                "generation_request_seconds",
                "End-to-end generation latency, admission to last token",
                ["model"]),
            "prefill": reg.histogram(
                "generation_prefill_seconds",
                "Prefill dispatch latency (prompt -> first token)",
                ["model"]),
            "itl": reg.histogram(
                "generation_inter_token_seconds",
                "Inter-token latency across live sequences", ["model"]),
            "depth": reg.gauge(
                "generation_queue_depth",
                "Generation requests waiting for prefill", ["model"]),
            "occ": reg.gauge(
                "generation_decode_occupancy",
                "Live sequences / decode bucket of the last step",
                ["model"]),
            "active": reg.gauge(
                "generation_active_sequences",
                "Sequences currently in the decode batch", ["model"]),
            "requests": reg.counter(
                "generation_requests_total",
                "Generation requests finished successfully", ["model"]),
            "tokens": reg.counter(
                "generation_tokens_total",
                "Tokens generated across all sequences", ["model"]),
            "steps": reg.counter(
                "generation_decode_steps_total",
                "Decode steps dispatched", ["model"]),
            "context": reg.counter(
                "generation_decode_context_tokens_total",
                "Cached tokens the decode steps attended over: the live "
                "sequences' context lengths, summed over steps",
                ["model"]),
            "window": reg.counter(
                "generation_decode_window_tokens_total",
                "Cached tokens a sliding-window layer's decode attended "
                "over: the smaller of a live sequence's context length "
                "and the window, summed over steps (and over the model's "
                "windows, where it has several); against "
                "generation_decode_context_tokens_total, what a walk of "
                "the whole context would have read, it is what the "
                "window saves", ["model"]),
            "table_rows": reg.counter(
                "generation_block_table_rows_built_total",
                "Block-table rows built: one a sequence that joined the "
                "decode batch (admitted, or re-prefilled after a hot swap "
                "or a state hazard); a decode step builds none",
                ["model"]),
            "compiles": reg.counter(
                "generation_compiles_total",
                "Cold prefill/decode shapes: calls whose program was not "
                "yet in the backend's jit cache, one a bucket however many "
                "programs it compiled (compile_requests_total{scope} counts "
                "those); flat after warmup", ["model"]),
            "errors": reg.counter(
                "generation_dispatch_errors_total",
                "Prefill/decode attempts that raised (chaos or backend "
                "fault)", ["model"]),
            "reprefills": reg.counter(
                "generation_reprefills_total",
                "Live sequences re-prefilled after a backend hot swap",
                ["model"]),
            "tenant_req": reg.counter(
                "serving_tenant_requests_total",
                "Requests answered successfully per model and tenant "
                "(the per-tenant SLO good-counter)",
                ["model", "tenant"]),
            "tenant_tok": reg.counter(
                "generation_tenant_tokens_total",
                "Tokens generated per model and tenant (the per-tenant "
                "tokens/sec signal the autoscaler scales on)",
                ["model", "tenant"]),
        }

    # -- registration -------------------------------------------------

    def _weight_fn(self, entry):
        overrides = entry.tenant_weights
        policy = self.tenants

        def weight(tenant):
            w = overrides.get(tenant)
            return policy.weight(tenant) if w is None else float(w)
        return weight

    def register(self, name, backend, decode_buckets=None,
                 prefill_buckets=None, max_queue=None, buckets=None,
                 tenant_weights=None):
        """Register an :class:`LMBackend` and start its generation loop.

        ``decode_buckets`` ride the registry entry's bucket slot (they
        are batch buckets, exactly like the classifier lane's);
        ``buckets`` is an alias for it, so a
        :class:`~.replication.ReplicaGroup` can stamp models through the
        classifier-shaped ``register`` signature.  ``prefill_buckets``
        are prompt-length pad targets, clipped to the model's
        ``seq_len``.  ``tenant_weights`` overrides WFQ weights for this
        model.
        """
        if not isinstance(backend, LMBackend):
            raise MXNetError(
                "generation lane serves LMBackend models, got %r"
                % (type(backend).__name__,))
        entry = self.registry.register(
            name, backend,
            buckets=(decode_buckets or buckets or
                     default_decode_buckets()),
            max_queue=max_queue, tenant_weights=tenant_weights)
        lane = _GenLane(entry, weight_fn=self._weight_fn(entry))
        seq_len = backend.cfg["seq_len"]
        lane_prefill = sorted({min(b, seq_len) for b in
                               (prefill_buckets or
                                default_prefill_buckets())})
        # stash on the lane (the registry entry's buckets stay the
        # decode ladder the swap-compat check sees)
        self._prefill_buckets = getattr(self, "_prefill_buckets", {})
        self._prefill_buckets[name] = lane_prefill
        for key, attr in (("req", "m_req"), ("prefill", "m_prefill"),
                          ("itl", "m_itl"), ("depth", "m_depth"),
                          ("occ", "m_occ"), ("active", "m_active"),
                          ("requests", "m_requests"),
                          ("tokens", "m_tokens"), ("steps", "m_steps"),
                          ("context", "m_context"),
                          ("window", "m_window"),
                          ("compiles", "m_compiles"),
                          ("errors", "m_errors"),
                          ("reprefills", "m_reprefills"),
                          ("table_rows", "m_table_rows")):
            setattr(lane, attr, self._fam[key].labels(name))
        with self._cond:
            self._lanes[name] = lane
        lane.thread = threading.Thread(
            target=self._loop, args=(name, lane),
            name="%s-generate-%s" % (self.name, name), daemon=True)
        lane.thread.start()
        return entry

    def swap(self, name, backend):
        """Hot reload (new weights + fresh cache as one unit)."""
        return self.registry.swap(name, backend)

    def warmup(self, name):
        """Pre-compile every prefill bucket (B=1) and decode bucket,
        with the pool write that follows each, so steady-state
        generation never compiles.  The largest decode bucket, the one
        a full batch runs ahead in, also makes a queued step and is
        answered by it: the program fed the device's own ids.  Returns
        the cold count, which ``generation_compiles_total`` takes: one a
        bucket whose program was not yet in the backend's jit cache,
        however many programs the bucket compiled (its pool write, the
        queued step); every program is counted in
        ``compile_requests_total{scope}``.  Each bucket runs under a
        start-up scope (``warmup.prefill`` / ``warmup.decode``,
        ``program`` ``prefill:<bucket>`` / ``decode:<bucket>``) and the
        table of what each held is logged at the end
        (:mod:`mxnet_tpu.compile_cache`)."""
        lane = self._lane(name)
        entry = lane.entry
        cold_n = 0
        scopes = []
        with entry.dispatch_lock:
            backend = entry.backend
            cache = backend.cache
            sid = "__warm"
            # positions 0 (prefill), 1 (decode), 2 (the queued step)
            cache.allocate(sid, 3)
            try:
                for t in self._prefill_buckets[name]:
                    with _compile_cache.scope(
                            "warmup.prefill",
                            _program_label(("prefill", t))) as sc:
                        _, k, v, cold, *state = backend.prefill(
                            _np.zeros(t, dtype=_np.int32), 1)
                        cache.write_prefill(sid, k, v, 1, *state)
                    scopes.append(sc)
                    cold_n += bool(cold)
                for b in entry.buckets:
                    with _compile_cache.scope(
                            "warmup.decode",
                            _program_label(("decode", b))) as sc:
                        tables = _np.stack(
                            [cache.block_table(
                                sid, backend.max_blocks_per_seq)] * b)
                        step = [_np.zeros(b, _np.int32),
                                _np.ones(b, _np.int32), tables,
                                _np.full(b, 2, _np.int32)]
                        backend.run_ahead = b == entry.buckets[-1]
                        cold = backend.decode(*step)[3]
                        if backend.run_ahead:
                            backend.run_ahead = False
                            backend.decode(backend.greedy_ids, step[1] + 1,
                                           tables, step[3] + 1)
                    scopes.append(sc)
                    cold_n += bool(cold)
            finally:
                backend.run_ahead = False
                backend.drop_ahead()
                cache.free(sid)
        if cold_n and _metrics.metrics_enabled():
            lane.m_compiles.inc(cold_n)
        _compile_cache.log_table("warmup of %r" % name, scopes)
        return cold_n

    # -- admission ----------------------------------------------------

    def _lane(self, name):
        with self._cond:
            lane = self._lanes.get(name)
        if lane is None:
            self.registry.get(name)
            raise _admission.UnknownModelError(
                "model %r has no generation lane" % (name,))
        return lane

    def submit(self, name, prompt, max_new_tokens=None, eos_id=None,
               deadline_ms=None, tenant=None, force=False):
        """Admit one generation request; returns its
        :class:`GenerationRequest` (stream + future).  ``tenant``
        labels it for WFQ/quotas (the tokens budget is charged
        ``max_new_tokens`` up front — a reservation, so admission is
        the only quota door).  ``force=True`` re-admits accepted work
        from a dead peer past overload/drain/quota (the affinity
        router's brownout contract); kill and fencing still refuse."""
        tenant = _tenancy.clean_tenant(tenant)
        try:
            return self._submit(name, prompt, max_new_tokens, eos_id,
                                deadline_ms, tenant, force)
        except _admission.ServingError as exc:
            if _tracing.tracing_enabled():
                _tracing.record_span(
                    "serving.shed", cat="serving", model=name,
                    reason=_admission.reject_reason(exc) or "error",
                    tenant=tenant, error=type(exc).__name__)
            raise

    def _submit(self, name, prompt, max_new_tokens, eos_id, deadline_ms,
                tenant, force):
        if self._killed or self._fenced_epoch is not None:
            raise _admission.ReplicaDeadError(
                "replica %r is %s" % (self.name,
                                      "fenced at epoch %r" % self._fenced_epoch
                                      if self._fenced_epoch is not None
                                      else "dead"))
        lane = self._lane(name)
        backend = lane.entry.backend
        prompt = _np.asarray(prompt, dtype=_np.int32).reshape(-1)
        if prompt.size < 1:
            raise MXNetError("empty prompt")
        if max_new_tokens is None:
            max_new_tokens = default_max_new_tokens()
        max_new_tokens = int(max_new_tokens)
        if max_new_tokens < 1:
            raise MXNetError("max_new_tokens must be >= 1")
        seq_len = backend.cfg["seq_len"]
        if prompt.size + max_new_tokens > seq_len:
            raise MXNetError(
                "prompt (%d) + max_new_tokens (%d) exceeds the model's "
                "seq_len %d" % (prompt.size, max_new_tokens, seq_len))
        vocab = backend.cfg["num_classes"]
        if prompt.min() < 0 or prompt.max() >= vocab:
            raise MXNetError("prompt token ids outside [0, %d)" % vocab)
        deadline = _admission.deadline_from_ms(deadline_ms)
        req = GenerationRequest(name, prompt, max_new_tokens, eos_id,
                                deadline, tenant)
        req.trace = _tracing.capture_wire_context()
        with _tracing.span("serving.admit", cat="serving", model=name,
                           tenant=tenant):
            chaos.visit("serving.admit", name=name)
            with self._cond:
                if self._stopping and not force:
                    self.admission.reject(name, "draining", tenant=tenant)
                if not force:
                    self.admission.admit(name, len(lane.queue),
                                         lane.entry.max_queue, deadline,
                                         tenant=tenant)
                    # tokens budget charged up front (max_new_tokens is
                    # the reservation): one admission-time verdict, no
                    # mid-generation quota kills
                    over = self.tenants.charge(tenant,
                                               tokens=max_new_tokens)
                    if over is not None:
                        self.admission.quota_reject(name, tenant, *over)
                lane.queue.push(tenant, req)
                if _metrics.metrics_enabled():
                    lane.m_depth.set(len(lane.queue))
                    pair = lane.tenant_handles.get(tenant)
                    if pair is None:
                        pair = lane.tenant_handles[tenant] = (
                            self._fam["tenant_req"].labels(name, tenant),
                            self._fam["tenant_tok"].labels(name, tenant))
                    req._h_tenant, req._h_tokens = pair
                self._cond.notify_all()
        return req

    def generate(self, name, prompt, max_new_tokens=None, eos_id=None,
                 deadline_ms=None, timeout=60.0, tenant=None):
        """Synchronous convenience: :meth:`submit` + ``result()``."""
        return self.submit(name, prompt, max_new_tokens=max_new_tokens,
                           eos_id=eos_id, deadline_ms=deadline_ms,
                           tenant=tenant).result(timeout=timeout)

    # -- the generation loop ------------------------------------------

    def _loop(self, name, lane):
        while True:
            self.last_beat = time.monotonic()  # graftcheck: disable=lock-discipline
            with self._cond:
                while (not lane.queue and not lane.active
                       and not self._killed and not self._stopping):
                    with _tracing.span("generation.idle", cat="serving",
                                       model=name):
                        self._cond.wait(0.05)
                    self.last_beat = time.monotonic()
                if self._killed or (self._stopping and not lane.queue
                                    and not lane.active):
                    lane.entry.backend.drop_ahead()
                    self._wake_owed(lane)
                    return
            self._iterate(name, lane)

    def _iterate(self, name, lane):
        """ONE iteration: retire finished/cancelled sequences, admit
        waiting requests up to the decode capacity, then run one decode
        step — the Orca schedule."""
        entry = lane.entry
        with _tracing.span("generation.iterate", cat="serving",
                           model=name) as sp, entry.dispatch_lock:
            backend = entry.backend
            self._retire_stale_backend(name, lane, backend)
            self._retire(lane, backend)
            capacity = entry.buckets[-1] - len(lane.active)
            with self._cond:
                # DRR admission into the decode batch: freed slots are
                # shared by tenant weight, not arrival order
                admitted = lane.queue.take(capacity)
                if _metrics.metrics_enabled():
                    lane.m_depth.set(len(lane.queue))
            for req in admitted:
                self._prefill_one(name, lane, backend, req)
            self._retire(lane, backend)
            sp.set(admitted=len(admitted), rows=len(lane.active))
            if lane.active:
                self._decode_step(name, lane, backend)
            self._retire(lane, backend)
            if not lane.active:
                self._wake_owed(lane)   # no device call to do it beside
                # an idle lane keeps no sequence, nor through one the
                # backend it ran on (a swap may have replaced it)
                lane.seated, lane.tables = [], None
            if _metrics.metrics_enabled():
                lane.m_active.set(len(lane.active))

    def _retire_stale_backend(self, name, lane, backend):
        """Hot swap landed: live sequences hold pages of the OLD
        backend's cache — re-prefill them (prompt + tokens so far) on
        the new one.  Caller holds dispatch_lock."""
        stale = [s for s in lane.active if s.backend_ref is not backend]
        if not stale:
            return
        for seq in stale:
            seq.backend_ref.drop_ahead()
            self._resume(name, lane, backend, seq, "hot swap")

    def _resume(self, name, lane, backend, seq, why):
        """Re-prefill one live sequence on ``backend`` over its prompt
        and the tokens it has (its old blocks, and state slot, freed
        where it held them).  Returns whether it goes on (``None``: it
        was over already); a sequence whose re-prefill fails is
        failed."""
        lane.active.remove(seq)
        # after a swap the old backend (and usually its cache) is on the
        # way out, but freeing keeps its occupancy gauges honest during
        # the brownout window where both backends are alive
        seq.backend_ref.cache.free(seq.seq_id)
        if seq.req.cancelled or seq.req.done:
            return None
        try:
            self._start_sequence(name, lane, backend, seq.req, resume=seq)
        except Exception as exc:  # noqa: BLE001 - fault path
            seq.req._fail(exc if isinstance(exc, MXNetError) else
                          MXNetError("re-prefill after %s failed: %s"
                                     % (why, exc)))
            return False
        if _metrics.metrics_enabled():
            lane.m_reprefills.inc()
        return True

    def _retire(self, lane, backend):
        """Free cache blocks of finished/cancelled sequences."""
        for seq in list(lane.active):
            req = seq.req
            finished = (seq.new_tokens >= req.max_new_tokens
                        or (req.eos_id is not None and seq.new_tokens
                            and req.generated
                            and req.generated[-1] == req.eos_id))
            if req.cancelled and not req.done:
                req._finish("cancelled", owed=lane.owed)
            elif finished and not req.done:
                req._finish("length" if seq.new_tokens
                            >= req.max_new_tokens else "stop",
                            owed=lane.owed)
                if _metrics.metrics_enabled():
                    lane.m_requests.inc()
                    if req._h_tenant is not None:
                        req._h_tenant.inc()
                    lane.m_req.observe(req.latency_s, req.trace)
                _emit_event("generation.complete", model=req.model,
                            tokens=seq.new_tokens,
                            reason=req.finish_reason)
            if req.done:
                backend.cache.free(seq.seq_id)
                lane.active.remove(seq)

    def _pick_prefill_bucket(self, name, t):
        for b in self._prefill_buckets[name]:
            if b >= t:
                return b
        return self._prefill_buckets[name][-1]

    def _prefill_one(self, name, lane, backend, req, resume=None):
        """Admit one request into the decode batch: deadline re-check,
        cache allocation (typed 429 on exhaustion), ONE prefill
        dispatch, first token out.  Caller holds dispatch_lock."""
        now = time.monotonic()
        if resume is None and _tracing.tracing_enabled():
            # how long the request waited for this moment, under its own
            # root span and not the loop's
            _tracing.record_span(
                "generation.queue", cat="serving",
                start_us=req.t_admit * 1e6, end_us=now * 1e6,
                parent=req.trace or 0, model=name, tenant=req.tenant,
                request=req.trace)
        if req.cancelled:
            req._finish("cancelled")
            return
        if _admission.AdmissionController.expired(req.deadline, now):
            self.admission.account(name, "deadline", req.tenant)
            req._fail(_admission.DeadlineExceededError(
                "model %r: deadline expired while queued (waited %.3fs)"
                % (name, now - req.t_admit)))
            return
        try:
            self._start_sequence(name, lane, backend, req, resume=resume)
        except CacheExhaustedError as exc:
            self.admission.account(name, "cache_exhausted", req.tenant)
            if _tracing.tracing_enabled():
                _tracing.record_span(
                    "serving.shed", cat="serving", model=name,
                    reason="cache_exhausted", parent=req.trace,
                    error=type(exc).__name__)
            req._fail(exc)
        except Exception as exc:  # noqa: BLE001 - fault path
            if _metrics.metrics_enabled():
                lane.m_errors.inc()
            req._fail(exc if isinstance(exc, MXNetError) else
                      MXNetError("prefill failed: %s" % exc))

    def _start_sequence(self, name, lane, backend, req, resume=None):
        """Allocate pages, run the prefill dispatch, join the decode
        batch.  ``resume`` re-prefills an existing sequence (hot swap)
        over prompt + already-generated tokens."""
        # on resume the LAST generated token stays OUT of the prefill:
        # its K/V is written by the next decode step (it is that step's
        # input), exactly as in the uninterrupted schedule — prefilling
        # it too would key it at two positions and break parity
        tokens = req.prompt if resume is None else _np.concatenate(
            [req.prompt,
             _np.asarray(req.generated[:-1], dtype=_np.int32)])
        t = int(tokens.size)
        lane.seq_counter += 1
        seq_id = "%s/%d" % (name, lane.seq_counter)
        # reserve the whole horizon up front: mid-generation allocation
        # cannot fail, so accepted sequences always run to completion
        budget = int(req.prompt.size) + req.max_new_tokens
        backend.cache.allocate(seq_id, min(budget, backend.cfg["seq_len"]))
        bucket = self._pick_prefill_bucket(name, t)
        padded = _np.zeros(bucket, dtype=_np.int32)
        padded[:t] = tokens
        t0 = time.monotonic()
        last_exc = None
        out = None
        for attempt in range(default_retries() + 1):
            if self._killed:
                break
            try:
                with _tracing.span("generation.prefill", cat="serving",
                                   model=name, bucket=bucket, length=t,
                                   attempt=attempt, parent=req.trace,
                                   request=req.trace) as sp:
                    try:
                        chaos.visit("serving.dispatch",
                                    name="%s:prefill:%d" % (name, bucket))
                        out = self._beside(lane, backend,
                                           backend.prefill, padded, t)
                    except Exception as exc:  # noqa: BLE001
                        sp.set(error=type(exc).__name__)
                        raise
                break
            except Exception as exc:  # noqa: BLE001 - fault path
                if _metrics.metrics_enabled():
                    lane.m_errors.inc()
                last_exc = exc
        if out is None:
            backend.cache.free(seq_id)
            raise MXNetError(
                "model %r: prefill failed after %d attempts: %s"
                % (name, default_retries() + 1, last_exc))
        logits, k, v, cold, *state = out
        if cold and _metrics.metrics_enabled():
            lane.m_compiles.inc()
        with _tracing.span("generation.prefill_write", cat="serving",
                           model=name, request=req.trace):
            # the pool write follows the dispatch that succeeded and targets
            # only this sequence's own reserved slots (and its state slot)
            try:
                backend.moved("prefill", h2d=backend.cache.write_prefill(
                    seq_id, k, v, t, *state))
            except Exception as exc:
                backend.cache.free(seq_id)
                if isinstance(exc, CachePoolLostError):
                    self._fail_live(lane, exc)
                raise
            seq = _Sequence(req, seq_id, backend)
            if _metrics.metrics_enabled():
                lane.m_table_rows.inc()
            seq.length = t
            if resume is None:
                first = int(_np.argmax(logits))
                req._push(first)
                wake = req._deliver()  # a first token waits for nothing
                if wake is not None:
                    wake()
                seq.last_token = first
                seq.new_tokens = 1
            else:
                # resumed sequence: tokens so far already streamed; the next
                # decode step continues from the last generated token
                seq.last_token = int(req.generated[-1])
                seq.new_tokens = resume.new_tokens
            req.seq_id = seq_id
            lane.active.append(seq)
            if _metrics.metrics_enabled():
                lane.m_prefill.observe(time.monotonic() - t0, req.trace)

    @staticmethod
    def _beside(lane, backend, call, *args, run_ahead=False):
        """``call(*args)``, a prefill or decode call of ``backend``, with
        the last decode step's tokens released to their streams while
        the device runs it.  The threads the tokens wake (a front end's
        stream writer, their clients) take the interpreter: woken as
        each token was known they held back the loop between two calls,
        woken before the call its dispatch, the device idle meanwhile
        (23 ms of a 100 ms step with 64 callers).  A front end's writer
        is woken once, whatever the rows (its requests return the same
        wake-up); a reader in process is woken by its own request.  An
        :class:`LMBackend` calls
        ``beside_device`` once its program is on the way.  ``run_ahead``
        is the loop's word, for this decode call alone, that the step
        after it may be queued too."""
        def deliver():
            lane.owed.update(seq.req._deliver() for seq in lane.active)
            GenerationScheduler._wake_owed(lane)

        backend.beside_device, backend.run_ahead = deliver, run_ahead
        try:
            return call(*args)
        finally:
            backend.beside_device, backend.run_ahead = None, False

    @staticmethod
    def _wake_owed(lane):
        """Wake the stream readers that are owed it: one call a front
        end, whatever the number of its rows."""
        owed, lane.owed = lane.owed, set()
        owed.discard(None)
        for wake in owed:
            wake()

    @staticmethod
    def _fail_live(lane, error):
        """Fail every live sequence of ``lane`` (their pages are gone);
        the next ``_retire`` frees their blocks."""
        for seq in lane.active:
            seq.req._fail(error)

    def _resume_live(self, name, lane, backend, error):
        """The recurrent state a retry would read is overwritten
        (:class:`RecurrentStateHazard`): re-prefill every live sequence
        over its prompt and the tokens it has, as after a hot swap, so
        that the next step reads a state every position entered once;
        a sequence whose re-prefill fails is failed.  No token is ever
        served from the overwritten state."""
        for seq in list(lane.active):
            resumed = self._resume(name, lane, backend, seq, error)
            if resumed is not None:
                _M_STATE_HAZARD.labels(
                    name, "resumed" if resumed else "failed").inc()

    def _may_run_ahead(self, lane, live):
        """May the decode call over ``live`` also queue the step after
        it?  Only where no request could be admitted before that step
        is answered, so that no prefill ever waits behind a queued step
        (a first token would wait a whole step longer): after this step
        the decode batch has no free slot — it is full, and no row
        reaches its ``max_new_tokens`` in this step (one that does in
        the *next* step rides the queued step and blocks the one
        after), is cancelled, or may stop at an ``eos_id`` no one can
        foresee — and no drain, close, kill or fence is under way.
        Everywhere else the step is dispatched alone, as ever.  (A swap
        lands between two iterations; the old backend's queued step is
        dropped when its sequences are re-prefilled.)"""
        if (len(live) < lane.entry.buckets[-1] or self._killed
                or self._stopping or self._fenced_epoch is not None
                or self.admission.draining):
            return False
        return all(seq.new_tokens + 1 < seq.req.max_new_tokens
                   and seq.req.eos_id is None and not seq.req.cancelled
                   for seq in live)

    def _decode_step(self, name, lane, backend):
        """ONE iteration-level decode step over every live sequence,
        padded to the decode bucket.  Caller holds dispatch_lock."""
        live = lane.active
        n = len(live)
        bucket = lane.entry.pick_bucket(n)
        with _tracing.span("decode.build", cat="serving", model=name):
            if lane.seated != live or len(lane.tables) != bucket:
                # the batch gained or lost a sequence: its table is put
                # together anew from the rows the sequences brought, as a
                # new array (the one handed over before is never written:
                # :meth:`LMBackend.decode`); any other step hands the
                # backend the very array the last one did
                tables = _np.zeros((bucket, backend.max_blocks_per_seq),
                                   dtype=_np.int32)
                tables[:n] = [seq.table for seq in live]
                lane.seated, lane.tables = list(live), tables
                # a step queued for the batch as it was answers no one: a
                # new row may hold the old row's blocks, position and token
                backend.drop_ahead()
            tables = lane.tables
            # the three vectors of a step are new arrays every step; a pad
            # row reads position 0 and context 1
            tokens = _np.zeros(bucket, dtype=_np.int32)
            positions = _np.zeros(bucket, dtype=_np.int32)
            tokens[:n] = [seq.last_token for seq in live]
            positions[:n] = [seq.length for seq in live]
            context = positions + 1
            req_uids = ([s.req.trace for s in live]
                        if _tracing.tracing_enabled() else ())
        out = None
        last_exc = None
        for attempt in range(default_retries() + 1):
            if self._killed:
                break
            try:
                with _tracing.span("generation.decode", cat="serving",
                                   model=name, bucket=bucket, rows=n,
                                   attempt=attempt,
                                   requests=req_uids) as sp:
                    try:
                        chaos.visit("serving.decode",
                                    name="%s:%d" % (name, bucket))
                        out = self._beside(
                            lane, backend, backend.decode, tokens,
                            positions, tables, context,
                            run_ahead=self._may_run_ahead(lane, live))
                    except Exception as exc:  # noqa: BLE001
                        sp.set(error=type(exc).__name__)
                        raise
                break
            except Exception as exc:   # noqa: BLE001 - fault path
                # a retry dispatches program and write again (the write
                # stores the same values); nothing stays queued
                backend.drop_ahead()
                if _metrics.metrics_enabled():
                    lane.m_errors.inc()
                last_exc = exc
                if isinstance(exc, (CachePoolLostError,
                                    RecurrentStateHazard)):
                    break       # what a retry would read is gone
        if self._killed:
            backend.drop_ahead()
            for seq in live:
                seq.req._fail(_admission.ReplicaDeadError(
                    "replica %r died mid-generation" % self.name))
            return
        if out is None and isinstance(last_exc, RecurrentStateHazard):
            self._resume_live(name, lane, backend, last_exc)
            return
        if out is None:
            # the step's logits or its K/V are lost (and after
            # CachePoolLostError the whole pool, rebuilt zeroed): no
            # live sequence can go on
            self._fail_live(
                lane, last_exc if isinstance(last_exc, CachePoolLostError)
                else MXNetError(
                    "model %r: decode step failed after %d attempts: %s"
                    % (name, default_retries() + 1, last_exc)))
            return
        with _tracing.span("decode.publish", cat="serving", model=name):
            cold = out[3]
            # what is served is what the next step is fed: the ids the
            # decode program chose from these very logits
            tokens_out = backend.greedy_ids
            now = time.monotonic()
            lane.steps += 1
            lane.rows += n
            lane.slots += bucket
            lane.max_step_rows = max(lane.max_step_rows, n)
            if _metrics.metrics_enabled():
                lane.m_steps.inc()
                lane.m_context.inc(int(context[:n].sum()))
                for window in getattr(backend, "windows", ()):
                    lane.m_window.inc(
                        int(_np.minimum(context[:n], window).sum()))
                lane.m_occ.set(n / float(bucket))
                if cold:
                    lane.m_compiles.inc()
            for i, seq in enumerate(live):
                seq.length += 1
                tok = int(tokens_out[i])
                seq.req._push(tok)
                seq.last_token = tok
                seq.new_tokens += 1
                lane.tokens += 1
                if _metrics.metrics_enabled():
                    lane.m_tokens.inc()
                    if seq.req._h_tokens is not None:
                        seq.req._h_tokens.inc()
                    lane.m_itl.observe(now - seq.t_last_token, seq.req.trace)
                seq.t_last_token = now

    # -- lifecycle ----------------------------------------------------

    @property
    def alive(self):
        return not self._killed and self._fenced_epoch is None

    def ready(self):
        return self.alive and not self.admission.draining \
            and not self._stopping

    def queue_depth(self, name):
        with self._cond:
            lane = self._lanes.get(name)
            return len(lane.queue) if lane else 0

    def load(self):
        """Waiting + live sequences across lanes — the affinity
        router's imbalance/spill signal (:mod:`~.routing`)."""
        with self._cond:
            return sum(len(l.queue) + len(l.active)
                       for l in self._lanes.values())

    def stats(self, name):
        """Decode-step evidence for bench/tests: steps run, tokens
        produced, per-step occupancy, and the largest step batch (the
        iteration-level admission witness)."""
        lane = self._lane(name)
        occ = lane.rows / float(lane.slots) if lane.slots else 0.0
        return {"steps": lane.steps, "tokens": lane.tokens,
                "rows": lane.rows, "slots": lane.slots,
                "occupancy": occ, "max_step_rows": lane.max_step_rows,
                "active": len(lane.active),
                "kv_cache": lane.entry.backend.cache.stats()}

    def drain(self):
        self.admission.start_drain()

    def close(self, timeout=10.0):
        """Drain, let live sequences finish, stop generation threads."""
        self.drain()
        deadline = time.monotonic() + timeout
        while time.monotonic() < deadline:
            with self._cond:
                idle = not any(l.queue or l.active
                               for l in self._lanes.values())
            if idle:
                break
            time.sleep(0.005)
        with self._cond:
            self._stopping = True
            self._cond.notify_all()
        for lane in list(self._lanes.values()):
            if lane.thread is not None:
                lane.thread.join(timeout=timeout)

    def kill(self):
        """Crash simulation: fail queued and live generations with the
        typed replica-dead error so a router can finish them on a peer
        (full re-prefill there — this replica's KV pages die with it).
        Idempotent."""
        with self._cond:
            if self._killed:
                return
            self._killed = True
            orphans = []
            for lane in self._lanes.values():
                orphans.extend(lane.queue.drain())
                # live sequences die with their KV pages; _fail is
                # idempotent, so a decode step racing this kill cannot
                # double-resolve
                orphans.extend(s.req for s in lane.active
                               if not s.req.done)
                if _metrics.metrics_enabled():
                    lane.m_depth.set(0)
            self._cond.notify_all()
        err = _admission.ReplicaDeadError(
            "replica %r was killed with the request queued" % self.name)
        for req in orphans:
            req._fail(err)

    def fence(self, epoch):
        """Epoch fence (PR-3 semantics, same contract as
        :meth:`~.scheduler.Scheduler.fence`): refuse new work at the
        lost epoch and fail queued/live generations like
        :meth:`kill` so the new epoch's replicas take them over."""
        with self._cond:
            self._fenced_epoch = epoch
        self.kill()
