"""Token sampling (greedy / temperature / top-k) with a finite-ness guard.

Two entry points share one spelling of the draw (``_greedy`` / ``_draw``):

- :func:`sample_token` samples a batch of logits under ONE key and one
  temperature (eager, for callers outside the serving loop);
- :func:`sample_rows` is the serving engine's: every row under its own key
  ``fold_in(fold_in(base_key, rid), idx)`` and its own temperature, plus
  per-row NaN / Inf counts, in one jitted program (``sample_rows_packed``
  is the same program with one transfer each way, as the engine calls it).  A row's token is bitwise the one ``sample_token`` draws
  for that row alone under the same key and temperature.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


class NonFiniteLogitsError(FloatingPointError):
    """Non-finite logits reached the sampling boundary.

    W4A4+LRC inference is exactly the regime where activation outliers can
    blow through the quantized numerics (LQER, arXiv 2402.02446); argmax
    over NaN/Inf logits silently emits garbage tokens, so the serving
    engine samples with the guard on and turns this into a per-request
    structured failure instead of a corrupted completion.
    """


def non_finite_error(boundary: str, n_nan: int, n_inf: int,
                     size: int) -> NonFiniteLogitsError:
    """The one message for non-finite logits at ``boundary`` (``sampling``
    or ``prefill-chunk``), with the counts for diagnosis."""
    return NonFiniteLogitsError(
        f"non-finite logits at {boundary} boundary: {n_nan} NaN, "
        f"{n_inf} Inf of {size} entries")


@jax.jit
def count_non_finite(logits):
    """(NaN count, Inf count) over all of ``logits``: one program, read
    by the caller with one ``jax.device_get``."""
    return (jnp.isnan(logits).sum(dtype=jnp.int32),
            jnp.isinf(logits).sum(dtype=jnp.int32))


def _greedy(logits):
    return jnp.argmax(logits, axis=-1).astype(jnp.int32)


def _draw(logits, key, temperature, top_k: int = 0):
    logits = logits / temperature
    if top_k > 0:
        vals, _ = jax.lax.top_k(logits, top_k)
        cutoff = vals[..., -1:]
        # dtype-aware mask: -1e30 overflows float16 (max ~6.5e4) to -inf and
        # can NaN through downstream softmax arithmetic
        logits = jnp.where(logits < cutoff, jnp.finfo(logits.dtype).min, logits)
    return jax.random.categorical(key, logits, axis=-1).astype(jnp.int32)


def sample_token(logits, key, temperature: float = 0.0, top_k: int = 0,
                 check_finite: bool = False):
    """logits: (B, V) -> (B,) int32.

    ``check_finite=True`` raises :class:`NonFiniteLogitsError` (with NaN /
    Inf counts for diagnosis) before any token is drawn from bad logits.
    The check synchronizes on the device value, which is why it is opt-in.
    """
    if check_finite:
        n_nan, n_inf = jax.device_get(count_non_finite(logits))
        if n_nan or n_inf:
            raise non_finite_error("sampling", int(n_nan), int(n_inf),
                                   logits.size)
    if temperature <= 0.0:
        return _greedy(logits)
    return _draw(logits, key, temperature, top_k)


@jax.jit
def sample_rows(logits, base_key, rids, idx, temps):
    """Sample every row of ``logits`` (B, V) in one program; given a step's
    (B, S, V) logits it samples each row's last position, so the slice is
    not a program of its own.

    Row b draws under ``fold_in(fold_in(base_key, rids[b]), idx[b])`` —
    the engine's (seed, rid, token index) key — at ``temps[b]``: argmax
    where it is <= 0, else a categorical draw at that temperature.
    ``rids`` and ``idx`` are (B,) uint32, as ``fold_in`` takes its data;
    ``temps`` is (B,) float32.  Returns ``(tokens, n_nan, n_inf)``, each
    (B,) int32: the counts are per row, so the caller can fail one row's
    request and keep the others' tokens.
    """
    def one(row, rid, i, temp):
        key = jax.random.fold_in(jax.random.fold_in(base_key, rid), i)
        hot = temp > 0
        # a greedy row divides by 1, not 0: its draw is discarded anyway
        t = jnp.where(hot, temp, 1.0).astype(row.dtype)
        return jnp.where(hot, _draw(row[None], key, t)[0],
                         _greedy(row[None])[0])

    if logits.ndim == 3:
        logits = logits[:, -1]
    tokens = jax.vmap(one)(logits, rids, idx, temps)
    return (tokens, jnp.isnan(logits).sum(-1, dtype=jnp.int32),
            jnp.isinf(logits).sum(-1, dtype=jnp.int32))


@jax.jit
def sample_rows_packed(logits, base_key, rows):
    """:func:`sample_rows` with one host transfer each way, as the engine
    calls it (on the chip each small transfer costs about as much as a
    program).  ``rows`` (B, 3) uint32 holds each row's rid, token index and
    the float32 bits of its temperature; the result is one (3, B) int32
    array of tokens, NaN counts and Inf counts."""
    temps = jax.lax.bitcast_convert_type(rows[:, 2], jnp.float32)
    return jnp.stack(sample_rows(logits, base_key, rows[:, 0], rows[:, 1],
                                 temps))
