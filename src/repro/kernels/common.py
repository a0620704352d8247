"""Constants shared by the Pallas kernels and their pure-jnp oracles.

Two different "very negative" numbers exist for two different jobs, and the
distinction matters:

* ``NEG_INF`` — identity element for max-pooling accumulators. Must be the
  most negative finite float32 so that ``max(NEG_INF, x) == x`` for *every*
  finite ``x`` (a table row can legitimately hold -1e31; an init of -1e30
  would silently win the max). Used by the embedding-bag kernels and oracles.
* ``MASK_VALUE`` — additive mask for pre-softmax attention scores. Chosen
  large enough that ``exp(MASK_VALUE - m)`` underflows to 0 but small enough
  that masked-score arithmetic (subtracting running maxima, multiplying by
  scale factors) cannot overflow to -inf and poison the softmax with NaNs.
"""
from __future__ import annotations

from repro.spans import scope

NEG_INF = -3.0e38       # max-combiner identity (≈ most negative finite f32)
MASK_VALUE = -1e30      # attention score mask (softmax-safe)

# ---------------------------------------------------------------------------
# lane-packed row stores
# ---------------------------------------------------------------------------
LANES = 128             # TPU vector lanes: HBM/VMEM tiles are (8, 128) words


def rows_per_line(D: int) -> int:
    """Pool rows packed into one lane-dense line of ``max(D, LANES)`` words.

    Mosaic addresses HBM in whole 128-lane lines, so a row DMA of a narrow
    ``(R, D)`` pool (``D`` = 16, or 1 for a wide/linear part) is refused.
    The row kernels therefore view the pool as lines: ``128 // D`` rows per
    128-lane line when ``D`` divides 128, one row per line when ``D`` is a
    multiple of 128.
    """
    if D % LANES == 0:
        return 1
    if LANES % D:
        raise ValueError(f"row width {D} neither divides nor is a multiple "
                         f"of {LANES} lanes")
    return LANES // D


def lane_pack(pool):
    """(R, D) row store -> (ceil(R / P), P * D) lines, P = ``rows_per_line``.

    Row ``v`` sits in line ``v // P`` at lanes ``[(v % P) * D, +D)``; the
    tail line is zero-padded.
    """
    import jax.numpy as jnp
    R, D = pool.shape
    P = rows_per_line(D)
    n_lines = -(-R // P)
    with scope("emb_relayout"):
        if n_lines * P != R:
            pool = jnp.pad(pool, ((0, n_lines * P - R), (0, 0)))
        return pool.reshape(n_lines, P * D)


def lane_unpack(lines, R: int, D: int):
    """Inverse of ``lane_pack``: (n_lines, P * D) lines -> (R, D) rows."""
    with scope("emb_relayout"):
        return lines.reshape(-1, D)[:R]
