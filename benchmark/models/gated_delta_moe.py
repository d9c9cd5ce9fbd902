"""The Gated DeltaNet / gated-attention / sparse-expert family through
the program: ``models.gated_delta_moe`` served by ``LMBackend`` behind
``GenerationScheduler`` and the HTTP front end, as one chip's share of
an expert-parallel deployment (``deployment.experts`` of the
configuration: the router's published width, the experts held here).

The weights are the benchmark's input, made on the device from the seed
in the deployment's dtype under the program's checkpoint names; the
program and the plain reference both get them.  They are 7.3 GB for
``qwen3-next-ep4``, so a process keeps the seed's weights it made last
and hands the same arrays to whoever asks for that seed again (the
reference, after the window).

**What the driver keeps of a decode step's logits.**  The
``serve-closed`` driver keeps the logits of every decode step of a run
on the host until the reference has run, to hold the rows behind the
served tokens against it.  At this family's cell that is ``[128 rows,
37,984]`` float32 = 19.4 MB a step over the ~1,000 steps of a run: 19 GB
on a host of 40.  Where the configuration's ``deployment.serve`` gives
``checked_logit_parts`` ``P``, the backend this module builds hands the
driver, in place of a step's logits, one of ``P`` equal parts of the
vocabulary of every row (:class:`KeptLogits`): the part its position
names, ``position % P``, the values the timed path produced, exact.  A
row's difference from the reference's row is taken over that part, so
every ``P`` successive tokens of a sequence cover every column once, and
a fault of the head in any stretch of the vocabulary is met at every
``P``-th position of every checked request.  A part is one run of a row:
keeping it reads 1/P of the step's logits on the host (2.4 MB of 19.4
at ``P`` 8).  The first token's logits (a prefill's) are kept whole.
:func:`kept_part` is the rule; a control that is to be read like for
like takes its columns by it too.
"""

import gc

import numpy as np

INIT_STD = 0.02          # the family's initializer_range
DT_BIAS_STD = 0.1
DECAY_RATE = (0.001, 0.7)   # A = exp(A_log), uniform in log: exp(g) =
#                             exp(-A softplus(a + dt)) spans ~0.5-0.999

_made = {}               # seed -> weights, the last seed only


def program_config(cfg):
    """The program's configuration of the benchmark's file: the router
    as wide as published, the held experts, the deployment's context
    limit."""
    from mxnet_tpu.models import gated_delta_moe

    share = cfg["deployment"]["experts"]
    if share["held"] != cfg["num_experts"]:
        raise ValueError("num_experts counts the experts held here")
    published = dict(cfg, num_experts=share["published"])
    return gated_delta_moe.lm_config(
        published, seq_len=cfg["n_positions"],
        held=(share["first"], share["held"]))


def weight_shapes(cfg):
    from mxnet_tpu.models import gated_delta_moe

    return gated_delta_moe.param_shapes(program_config(cfg))


def weight_kind(name):
    for suffix, kind in (("gdn_norm_gamma", "one"), ("A_log", "decay"),
                         ("dt_bias", "dt")):
        if name.endswith(suffix):
            return kind
    return "normal"


def _draw(key, shape, kind, dtype):
    import jax
    import jax.numpy as jnp

    if kind == "one":
        return jnp.ones(shape, dtype)
    if kind == "decay":
        return jax.random.uniform(key, shape, jnp.float32,
                                  np.log(DECAY_RATE[0]),
                                  np.log(DECAY_RATE[1]))
    if kind == "dt":
        return DT_BIAS_STD * jax.random.normal(key, shape, jnp.float32)
    return (INIT_STD * jax.random.normal(key, shape, jnp.float32)
            ).astype(dtype)


def weight_key(seed):
    import jax

    return jax.random.PRNGKey(seed % (2 ** 31))


def make_weights(cfg, seed):
    """The seed's weights on the device, in the dtype the deployment
    serves in: normal(0, 0.02) matrices, embeddings and norm offsets (a
    zero-centred gain is ``1 + w``), the DeltaNet output norm's plain
    gain 1, ``A_log`` uniform between ``log 0.001`` and ``log 0.7`` and
    ``dt_bias`` normal(0, 0.1), both float32.  A leaf a call, one
    compiled program a shape.  The same arrays when the seed is asked
    for again."""
    import jax

    if seed not in _made:
        _made.clear()               # the former seed's go first, and
        gc.collect()                # what a former run left in cycles
        draw = jax.jit(_draw, static_argnums=(1, 2, 3))
        key = weight_key(seed)
        dtype = cfg["deployment"]["serve"]["dtype"]
        _made[seed] = {
            name: draw(jax.random.fold_in(key, i), shape,
                       weight_kind(name), dtype)
            for i, (name, shape) in enumerate(
                sorted(weight_shapes(cfg).items()))}
    return dict(_made[seed])


def kept_part(position, parts, vocab):
    """The columns kept of a row that consumed ``position``: a slice."""
    width = vocab // parts
    first = (int(position) % parts) * width
    return slice(first, first + width)


class KeptRow(object):
    """One row of :class:`KeptLogits`: ``row - reference_row`` is the
    difference over the kept part."""

    __slots__ = ("values", "part")

    def __init__(self, values, part):
        self.values, self.part = values, part

    def __sub__(self, reference_row):
        return self.values - np.asarray(reference_row)[self.part]


class KeptLogits(object):
    """Of one decode step's logits ``[B, V]``, as the program produced
    them, row ``i``'s part ``positions[i] % parts``.  One gather: the
    loop's thread gives the interpreter up once for it (every large
    numpy copy does, and gets it back behind 256 other threads)."""

    __slots__ = ("values", "parts", "vocab", "positions")

    def __init__(self, logits, positions, parts):
        self.vocab, self.parts = logits.shape[-1], int(parts)
        if self.vocab % self.parts:
            raise ValueError("%d columns do not make %d equal parts"
                             % (self.vocab, self.parts))
        self.positions = np.array(positions, dtype=np.int64)
        rows = len(self.positions)
        self.values = logits[:rows].reshape(rows, self.parts, -1)[
            np.arange(rows), self.positions % self.parts]

    def __len__(self):
        return len(self.values)

    def __getitem__(self, row):
        return KeptRow(self.values[row], kept_part(
            self.positions[row], self.parts, self.vocab))

    def __array__(self, *args, **kwargs):
        raise TypeError("a part of each row was kept for the benchmark's "
                        "comparison (greedy requests need none of it); "
                        "the whole logits are LMBackend.decode's")


def keeping_parts(base, parts):
    """``base`` (an ``LMBackend``) whose ``decode`` hands its caller the
    step's logits as :class:`KeptLogits`."""

    class Keeping(base):
        def decode(self, tokens, positions, block_tables, context_lens):
            out = base.decode(self, tokens, positions, block_tables,
                              context_lens)
            return (KeptLogits(out[0], positions, parts),) + tuple(out[1:])

    return Keeping


def build_backend(cfg, serve, weights, model_name, wrap):
    """``LMBackend`` handed this model's definition (weights, key and
    value pools in the deployment's dtype, a float32 state pool of
    ``state_slots`` slots), subclassed by ``wrap`` so the benchmark can
    put spans and counts around ``prefill`` and ``decode``."""
    import jax.numpy as jnp

    from mxnet_tpu import serving
    from mxnet_tpu.models import gated_delta_moe

    definition = gated_delta_moe.lm_definition(program_config(cfg),
                                               jnp.dtype(serve["dtype"]))
    base = serving.LMBackend
    if serve.get("checked_logit_parts"):
        base = keeping_parts(base, serve["checked_logit_parts"])
    return wrap(base)(
        weights, definition=definition, block_size=serve["block_size"],
        num_blocks=serve["num_blocks"], model=model_name,
        state_slots=serve["state_slots"])
