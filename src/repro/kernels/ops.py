"""Jit'd public wrappers around the Pallas kernels.

Handle padding to the chip's tiling, dtype plumbing, and the choice of
interpret mode. On the TPU every block is 128 wide in its last two
dimensions (Mosaic requires multiples of 8 and 128 there), so n, m, d and
the rank are zero-padded up to multiples of 128 — exact for the MU updates
and for distances, see each wrapper. On the CPU the kernels execute their
Python bodies under ``interpret=True`` (same BlockSpec walk), with 8-wide
blocks wherever 128 does not divide, which keeps the CPU tests fast.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from . import flash_attention as _fa
from . import nmf_update as _nmf
from . import pairwise_dist as _pd
from . import silhouette_sums as _ss


def _interpret_default() -> bool:
    """Interpret on the CPU, lower to Mosaic on the TPU; no other backend."""
    backend = jax.default_backend()
    if backend not in ("cpu", "tpu"):
        raise RuntimeError(
            f"Pallas kernels run on the TPU or interpreted on the CPU, not on {backend!r}"
        )
    return backend == "cpu"


def _lane_mult(interpret: bool) -> int:
    """Rank/lane padding multiple: the 128-lane MXU width on the TPU path,
    8 under interpret mode, where lane alignment buys nothing and
    128-padding tiny-k problems would only waste interpreter time."""
    return 8 if interpret else 128


def _block(size: int, interpret: bool) -> int:
    """Block edge along a tiled axis of ``size``: always 128 on the TPU
    path (the wrappers pad up to it); under interpret mode 128 where it
    divides and 8 otherwise, so the interpreter walks few grid steps."""
    return 128 if not interpret or size % 128 == 0 else 8


def _pad_to(x: jax.Array, axis: int, mult: int) -> jax.Array:
    size = x.shape[axis]
    pad = (-size) % mult
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


# -----------------------------------------------------------------------------
# NMF multiplicative updates
# -----------------------------------------------------------------------------
def mu_update_h(v: jax.Array, w: jax.Array, h: jax.Array, interpret: bool | None = None) -> jax.Array:
    """Fused H <- H * (W^T V)/(W^T W H + eps); pads n and m to the block
    (``_block``) and k to the lane width (``_lane_mult``). Zero rows of V
    and W add nothing to W^T V or W^T W, and zero columns of H stay zero,
    so the padding is exact."""
    interpret = _interpret_default() if interpret is None else interpret
    n, m = v.shape
    k = w.shape[1]
    bn, bm, bk = _block(n, interpret), _block(m, interpret), _lane_mult(interpret)
    vp = _pad_to(_pad_to(v, 0, bn), 1, bm)
    wp = _pad_to(_pad_to(w, 0, bn), 1, bk)
    hp = _pad_to(_pad_to(h, 0, bk), 1, bm)
    g = wp.T @ wp  # (kp, kp) — cheap, fp32
    out = _nmf.h_update(vp, wp, hp, g, bm=bm, bn=bn, interpret=interpret)
    return out[:k, :m].astype(h.dtype)


def mu_update_w(v: jax.Array, w: jax.Array, h: jax.Array, interpret: bool | None = None) -> jax.Array:
    """Fused W <- W * (V H^T)/(W H H^T + eps); padded like ``mu_update_h``."""
    interpret = _interpret_default() if interpret is None else interpret
    n, m = v.shape
    k = w.shape[1]
    bn, bm, bk = _block(n, interpret), _block(m, interpret), _lane_mult(interpret)
    vp = _pad_to(_pad_to(v, 0, bn), 1, bm)
    wp = _pad_to(_pad_to(w, 0, bn), 1, bk)
    hp = _pad_to(_pad_to(h, 0, bk), 1, bm)
    q = hp @ hp.T
    out = _nmf.w_update(vp, hp, wp, q, bm=bm, bn=bn, interpret=interpret)
    return out[:n, :k].astype(w.dtype)


# -----------------------------------------------------------------------------
# Pairwise distances
# -----------------------------------------------------------------------------
def pairwise_sq_dists(x: jax.Array, y: jax.Array | None = None, interpret: bool | None = None) -> jax.Array:
    interpret = _interpret_default() if interpret is None else interpret
    y = x if y is None else y
    (n, d), m = x.shape, y.shape[0]
    bn, bm, bd = _block(n, interpret), _block(m, interpret), _block(d, interpret)
    xp = _pad_to(_pad_to(x, 0, bn), 1, bd)
    yp = _pad_to(_pad_to(y, 0, bm), 1, bd)
    out = _pd.pairwise_sq_dists(xp, yp, bn=bn, bm=bm, bd=bd, interpret=interpret)
    return out[:n, :m]


def pairwise_sq_dists_batched(
    x: jax.Array, y: jax.Array | None = None, interpret: bool | None = None
) -> jax.Array:
    """Leading-axis batched pairwise distances: x (b, n, d), y (b, m, d).

    One kernel launch covers all b lanes — the entry point batched scorers
    use instead of vmapping the 2-D kernel. Zero padding of n/m/d to tile
    multiples is exact for distances; callers slice the result.
    """
    interpret = _interpret_default() if interpret is None else interpret
    y = x if y is None else y
    (_, n, d), m = x.shape, y.shape[1]
    bn, bm, bd = _block(n, interpret), _block(m, interpret), _block(d, interpret)
    xp = _pad_to(_pad_to(x, 1, bn), 2, bd)
    yp = _pad_to(_pad_to(y, 1, bm), 2, bd)
    out = _pd.pairwise_sq_dists_batched(xp, yp, bn=bn, bm=bm, bd=bd, interpret=interpret)
    return out[:, :n, :m]


# -----------------------------------------------------------------------------
# Streaming silhouette dist-sums (fused distance + cluster reduction)
# -----------------------------------------------------------------------------
def silhouette_dist_sums(
    x: jax.Array,
    onehot: jax.Array,
    y: jax.Array | None = None,
    interpret: bool | None = None,
) -> jax.Array:
    """(n, k) cluster distance sums ``sqrt(pairwise(x, y)) @ onehot`` without
    materializing the (n, m) distance matrix.

    x (n, d), y (m, d) (default x), onehot (m, k) with zero rows for
    masked/padded points. Zero-padding m is exact because padded one-hot
    rows are zero (their distances contract to nothing); zero-padding d is
    exact for distances; padded n rows and k columns are sliced off.
    """
    interpret = _interpret_default() if interpret is None else interpret
    y = x if y is None else y
    n, d = x.shape
    m, k = onehot.shape
    bn, bm, bd = _block(n, interpret), _block(m, interpret), _block(d, interpret)
    xp = _pad_to(_pad_to(x, 0, bn), 1, bd)
    yp = _pad_to(_pad_to(y, 0, bm), 1, bd)
    gp = _pad_to(_pad_to(onehot, 0, bm), 1, _lane_mult(interpret))
    out = _ss.silhouette_dist_sums(xp, yp, gp, bn=bn, bm=bm, bd=bd, interpret=interpret)
    return out[:n, :k]


def silhouette_dist_sums_batched(
    x: jax.Array,
    onehot: jax.Array,
    y: jax.Array | None = None,
    interpret: bool | None = None,
) -> jax.Array:
    """Leading-axis batched streaming dist-sums: x (b, n, d), onehot (b, m, k).

    One launch streams all b wavefront lanes; the (b, n, m) distance block
    the dense batched path would write to HBM never exists.
    """
    interpret = _interpret_default() if interpret is None else interpret
    y = x if y is None else y
    _, n, d = x.shape
    _, m, k = onehot.shape
    bn, bm, bd = _block(n, interpret), _block(m, interpret), _block(d, interpret)
    xp = _pad_to(_pad_to(x, 1, bn), 2, bd)
    yp = _pad_to(_pad_to(y, 1, bm), 2, bd)
    gp = _pad_to(_pad_to(onehot, 1, bm), 2, _lane_mult(interpret))
    out = _ss.silhouette_dist_sums_batched(xp, yp, gp, bn=bn, bm=bm, bd=bd, interpret=interpret)
    return out[:, :n, :k]


# -----------------------------------------------------------------------------
# Flash attention
# -----------------------------------------------------------------------------
def flash_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    causal: bool = True,
    window: int | None = None,
    scale: float | None = None,
    interpret: bool | None = None,
) -> jax.Array:
    """Causal/windowed GQA flash attention; pads L to tiles and D to lanes."""
    interpret = _interpret_default() if interpret is None else interpret
    b, hq, lq, d = q.shape
    lk = k.shape[2]
    scale = float(scale if scale is not None else d ** -0.5)
    bq = 128 if lq % 128 == 0 else 8
    bk = 128 if lk % 128 == 0 else 8
    dp = 128 if d % 128 == 0 else 8
    qp = _pad_to(_pad_to(q, 2, bq), 3, dp)
    kp = _pad_to(_pad_to(k, 2, bk), 3, dp)
    vp = _pad_to(_pad_to(v, 2, bk), 3, dp)
    # Padded kv rows sit at indices >= lk; with causal masking and lq == lk
    # no real query row can attend them (k_idx > q_idx), so zero-padding is
    # exact. Non-causal use requires pre-aligned lengths.
    if kp.shape[2] != lk:
        assert causal and lq == lk, "kv-length padding requires causal attention with lq == lk"
    out = _fa.flash_attention(
        qp, kp, vp, causal=causal, window=window, scale=scale, bq=bq, bk=bk, interpret=interpret
    )
    return out[:, :, :lq, :d]
