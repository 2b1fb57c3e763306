"""Streaming vs dense silhouette scoring: bytes moved + wall-clock.

The dense T_scorer path materializes the (n, n) distance matrix in HBM and
immediately reduces it to (n, k) cluster dist-sums — ~8n^2 bytes of traffic
(write + read back) for 4nk bytes of useful output. The streaming tiers
(`repro.core.scoring.cluster_dist_sums`: blocked jnp / fused Pallas) keep
every distance strip/tile on-chip, so traffic drops to the O(n*d + n*k)
operand/output floor.

Rows per n:
  scoring_dense_us_nX / scoring_stream_us_nX — wall-clock (dense skipped
      where the (n, n) block exceeds the scoring arena budget);
  scoring_bytes_ratio_nX — dense/stream bytes, measured via XLA
      ``cost_analysis`` when available, else the analytic traffic model;
  scoring_stream_ok_nX — 1.0 when streaming completed at an n whose dense
      (n, n) allocation is infeasible under the arena budget.

The arena budget models the per-score HBM slice a wavefront lane may claim
(many lanes share the device); quick mode uses 32 MiB so the regime where
dense dies but streaming survives is reachable on CPU in seconds.
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp

from repro.core import scoring
from repro.kernels import ops as kernel_ops

_D, _K = 32, 8


def _time(fn, *args, reps: int = 3) -> float:
    jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(reps):
        jax.block_until_ready(fn(*args))
    return (time.perf_counter() - t0) / reps * 1e6


def _measured_bytes(fn, *args) -> float | None:
    """XLA-reported HBM traffic for the compiled fn, when the backend says."""
    try:
        cost = jax.jit(fn).lower(*args).compile().cost_analysis()
        return float(cost["bytes accessed"])
    except Exception:
        return None


def _model_bytes_dense(n: int) -> float:
    # write D (4n^2) + read D back for the contraction (4n^2) + operands/out
    return 8.0 * n * n + 4.0 * n * (_D + 2 * _K)


def _model_bytes_stream(n: int, block_rows: int) -> float:
    # per strip: x block + full x + onehot re-read; out written once
    n_blocks = -(-n // block_rows)
    return 4.0 * (n_blocks * (block_rows * _D + n * _D + n * _K) + n * _K)


def run(quick: bool = True) -> list[tuple[str, float, str]]:
    key = jax.random.PRNGKey(0)
    sizes = [1024, 4096] if quick else [1024, 4096, 16384]
    budget = (32 if quick else 512) * 1024 * 1024  # scoring arena, bytes
    block_rows = 512
    rows: list[tuple[str, float, str]] = []

    def dense(x, onehot):
        return jnp.matmul(jnp.sqrt(scoring.pairwise_sq_dists(x)), onehot)

    def stream(x, onehot):
        return scoring._cluster_dist_sums_blocked(x, onehot, block_rows)

    # Pallas parity at a small n (interpret mode makes large-n timing moot —
    # on TPU the fused kernel replaces the blocked tier wholesale)
    x = jax.random.normal(key, (256, _D))
    onehot = jax.nn.one_hot(jax.random.randint(key, (256,), 0, _K), _K)
    err = float(
        jnp.max(jnp.abs(kernel_ops.silhouette_dist_sums(x, onehot) - dense(x, onehot)))
        / jnp.maximum(jnp.max(jnp.abs(dense(x, onehot))), 1e-12)
    )
    rows.append(("scoring_pallas_rel_err", err, "fused kernel vs dense oracle, n=256"))

    for n in sizes:
        kx, kl = jax.random.split(jax.random.fold_in(key, n))
        x = jax.random.normal(kx, (n, _D))
        onehot = jax.nn.one_hot(jax.random.randint(kl, (n,), 0, _K), _K)

        dense_bytes = _measured_bytes(dense, x, onehot) or _model_bytes_dense(n)
        stream_bytes = _measured_bytes(stream, x, onehot) or _model_bytes_stream(n, block_rows)
        rows.append(
            (
                f"scoring_bytes_ratio_n{n}",
                dense_bytes / stream_bytes,
                f"dense={dense_bytes / 1e6:.1f}MB stream={stream_bytes / 1e6:.1f}MB",
            )
        )

        dense_feasible = 4.0 * n * n <= budget
        if dense_feasible:
            us = _time(jax.jit(dense), x, onehot)
            rows.append((f"scoring_dense_us_n{n}", us, f"(n,n)={4.0 * n * n / 1e6:.0f}MB in arena"))
        else:
            rows.append(
                (
                    f"scoring_dense_us_n{n}",
                    float("inf"),
                    f"infeasible: (n,n)={4.0 * n * n / 1e6:.0f}MB > arena {budget / 1e6:.0f}MB",
                )
            )
        us = _time(jax.jit(stream), x, onehot)
        peak = 4.0 * block_rows * n
        rows.append((f"scoring_stream_us_n{n}", us, f"peak_strip={peak / 1e6:.1f}MB"))
        if not dense_feasible:
            rows.append(
                (
                    f"scoring_stream_ok_n{n}",
                    1.0,
                    f"streaming completed where dense (n,n) exceeds the {budget / 1e6:.0f}MB arena",
                )
            )
    return rows


if __name__ == "__main__":
    for r in run():
        print(r)
