"""The one traffic generator: a ring of training batches from a traffic file.

A traffic file (``traffic/<name>.json``) holds only parameters:

* ``zipf_alpha``   - skew of the sparse ids; 0 draws them uniformly;
* ``hot_rows``     - rows of the hot-row cache the job plans (0 = none);
* ``ring_batches`` - distinct batches made in set-up and fed round and round;
* ``ckpt_every``   - a layout-stamped flash checkpoint every N steps (0 = none).

Re-planning stays off, the launcher's default (``--replan-every 0``).

Every batch is drawn from its own stream, ``SeedSequence([seed, i])``, so
batch ``i`` is the same whatever the ring's length, and every seed gives the
same shapes. The ids follow the bounded-Zipf inverse CDF of
``repro.data.synthetic.zipf_indices`` (copied here, vectorized over tables,
so that the benchmark's traffic cannot change under a later PR), and the
labels carry the same learnable structure as ``criteo_batch``.
"""
from __future__ import annotations

from typing import Dict, List, Mapping, Sequence

import numpy as np

TRAFFIC_KEYS = ("zipf_alpha", "hot_rows", "ring_batches", "ckpt_every")


def check_traffic(traffic: Mapping) -> None:
    """Refuse a traffic file with missing, unknown or out-of-range keys."""
    missing = [k for k in TRAFFIC_KEYS if k not in traffic]
    unknown = [k for k in traffic if k not in TRAFFIC_KEYS]
    if missing or unknown:
        raise ValueError(f"traffic keys: missing {missing}, unknown {unknown}")
    if traffic["zipf_alpha"] < 0 or traffic["ring_batches"] < 4:
        raise ValueError(f"traffic out of range: {dict(traffic)}")
    for k in ("hot_rows", "ckpt_every"):
        if int(traffic[k]) < 0:
            raise ValueError(f"traffic {k} < 0: {traffic[k]}")


def zipf_ids(u: np.ndarray, rows, alpha: float) -> np.ndarray:
    """Bounded-Zipf ranks in ``[0, rows)`` from uniforms ``u`` in [0, 1).

    P(id = i) is proportional to (i + 1)^-alpha; ``rows`` broadcasts against
    ``u`` (one bound per table). ``alpha == 0`` gives uniform ids. For
    ``alpha > 0`` this is the continuous inverse CDF of ``zipf_indices``,
    draw for draw.
    """
    rows_f = np.asarray(rows, np.float64)
    rows_i = np.asarray(rows, np.int64)
    if alpha <= 0.0:
        return np.minimum((u * rows_f).astype(np.int64), rows_i - 1)
    if abs(alpha - 1.0) < 1e-9:
        x = np.exp(u * np.log(rows_f))
    else:
        x = ((rows_f ** (1.0 - alpha) - 1.0) * u + 1.0) ** (1.0 / (1.0 - alpha))
    return np.minimum(x.astype(np.int64), rows_i) - 1


def make_batch(seed: int, index: int, *, batch_size: int, n_dense: int,
               table_rows: Sequence[int], multi_hot: int,
               zipf_alpha: float) -> Dict[str, np.ndarray]:
    """Batch ``index`` of the ring for ``seed``: dense (B, n_dense) f32,
    sparse (B, T, H) int32 per-table-local ids, label (B,) f32."""
    rng = np.random.default_rng(np.random.SeedSequence([int(seed), int(index)]))
    B, T = batch_size, len(table_rows)
    dense = rng.standard_normal((B, n_dense), np.float32)
    u = rng.random((B, T, multi_hot))
    rows = np.asarray(table_rows, np.int64)[None, :, None]
    sparse = zipf_ids(u, rows, zipf_alpha)
    # criteo_batch's structure: a dense projection and the parity of the
    # first ids of the first two tables set the click probability
    w_dense = np.linspace(-1.0, 1.0, n_dense).astype(np.float32)
    logit = dense @ w_dense
    logit = logit + 0.5 * ((sparse[:, 0, 0] % 2) - 0.5) * 2
    logit = logit + 0.25 * ((sparse[:, 1 % T, 0] % 4 == 0) - 0.25) * 4
    p = 1.0 / (1.0 + np.exp(-logit))
    label = (rng.random(B) < p).astype(np.float32)
    return {"dense": dense, "sparse": sparse.astype(np.int32), "label": label}


def make_ring(seed: int, sizes: Mapping, traffic: Mapping,
              n: int = 0) -> List[Dict[str, np.ndarray]]:
    """The first ``n`` (default: ``ring_batches``) batches of the ring."""
    check_traffic(traffic)
    n = n or int(traffic["ring_batches"])
    return [make_batch(seed, i, batch_size=sizes["batch_size"],
                       n_dense=sizes["n_dense"],
                       table_rows=sizes["table_rows"],
                       multi_hot=sizes["multi_hot"],
                       zipf_alpha=float(traffic["zipf_alpha"]))
            for i in range(n)]
