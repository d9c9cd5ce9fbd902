"""The causal depthwise short convolution of the hybrid decoders, in its
two forms, and the tail a sequence keeps for it.

A layer convolves ``taps`` successive rows of its input, channel by
channel, the last tap on the current token.  A prefill does that over a
whole padded prompt (:func:`conv_prefill`) and hands back the ``taps -
1`` rows that went in last before ``length``: the **tail**, the
layer's state (or part of it) between steps.  A decode step
(:func:`conv_step`) puts the token's row behind the tail, convolves the
``taps`` rows and keeps the last ``taps - 1``.  Both return the float32
sum and leave what follows (a bias, an activation, a gate) to the
model: the Gated DeltaNet mixer (``models/gated_delta_moe.py``), the
gated short convolution (``models/short_conv_moe.py``) and the Mamba-2
mixer (``models/state_space_moe.py``) differ only there.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

__all__ = ["tail_shape", "conv_prefill", "conv_step"]


def tail_shape(rows, channels):
    """How ``rows`` rows of ``channels`` values lie in a state pool: as
    rows of 512 lanes where the channels are whole such rows (a pool
    ``[.., 3, 8192]`` would pad its 3 rows to a tile of 16 on a TPU)."""
    if channels % 512 == 0:
        return (rows * channels // 512, 512)
    return (rows, channels)


def conv_prefill(u, weight, length=None, tail=None):
    """One prompt, or a stretch of one.  ``u`` ``[T, channels]``;
    ``weight`` ``[channels, taps]``; ``tail`` ``[taps - 1, channels]``
    the rows before ``u`` (an empty state, zeros, if None).  Returns the
    convolution's float32 sum ``[T, channels]`` and the ``taps - 1``
    rows that went in last before ``length`` (``T`` if None; those of
    ``tail`` where ``u`` has too few), in ``u``'s dtype."""
    taps, t = weight.shape[1], u.shape[0]
    w = weight.astype(jnp.float32)
    if tail is None:
        padded = jnp.pad(u, ((taps - 1, 0), (0, 0)))
    else:
        padded = jnp.concatenate([tail.astype(u.dtype), u])
    conv = sum(padded[j:j + t].astype(jnp.float32) * w[:, j]
               for j in range(taps))
    tail = jax.lax.dynamic_slice_in_dim(
        padded, t if length is None else length, taps - 1)
    return conv, tail


def conv_step(tail, u, weight):
    """One token a sequence.  ``tail`` ``[B, taps - 1, channels]`` the
    rows before it, ``u`` ``[B, channels]`` the token's.  Returns the
    float32 sum ``[B, channels]`` and the tail, advanced."""
    window = jnp.concatenate([tail, u[:, None, :].astype(tail.dtype)],
                             axis=1)
    conv = jnp.einsum("bjc,cj->bc", window.astype(jnp.float32),
                      weight.astype(jnp.float32))
    return conv, window[:, 1:]
