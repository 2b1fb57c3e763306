"""Batched evaluation planes: mask-padded multi-k fits behind ``EvalPlane``.

These are the hardware-shaped back ends of the wavefront executor
(``repro.core.evalplane.WavefrontScheduler``): a whole frontier of k values
becomes ONE vmapped, jit'd fit at a common padded rank, so the per-k
trace/JIT/dispatch cost the thread path pays |wave| times is paid once.

Two dispatch modes, selected by the ``mesh=`` option:

  * **single-device** (``mesh=None``, default): the padded wave runs as one
    vmapped fit on the default device — PR 1's batched executor.
  * **mesh-sharded**: a 2-D ``Mesh((lane, data))`` splits the wave's k axis
    over the ``lane`` axis (each device group fits a disjoint slice of the
    padded ensemble via shard_map) and, for the NMFk plane, optionally
    shards V's rows over the ``data`` axis reusing the pyDNMFk psum
    structure — the paper's parallel-over-k × distributed-within-k
    composition inside one jit'd dispatch. Build the mesh with
    ``repro.launch.mesh.make_wave_mesh``.

Shape discipline (what keeps compile counts ~O(1) instead of O(|K|)):

  * the rank axis is padded to a fixed ``k_pad`` (default: the largest k
    the plane will ever see — pass the top of the search range);
  * the batch axis is bucketed by ``repro.factorization.batching.
    bucket_batch``: pow2 rounding with a floor of ``bucket_min`` (defaults
    to the mesh lane count so every dispatch splits evenly over lanes),
    and **reuse of already-compiled buckets** — a scalar fallback or an
    odd-sized wave rides the nearest compiled ``(batch, k_pad)`` shape
    instead of minting its own. ``WavefrontScheduler(max_wave=N)`` sets the
    plane's ``dispatch_cap`` so padding never exceeds an explicit memory
    bound; ``pad_batch=False`` disables pow2 bucketing (lane-multiple
    padding still applies under a mesh).

``shapes_compiled`` records the distinct (batch, k_pad) shapes dispatched —
a deterministic proxy for jit compilations that the wavefront benchmarks
compare against the thread path's one-compilation-per-distinct-k.

Telemetry: every dispatch observes ``lane_utilization`` (real lanes /
dispatched lanes) and, under a mesh, emits per-device-group ``lane`` spans
on ``device:{i}`` tracks so a Perfetto trace shows which ks each lane group
carried through the wave.
"""
from __future__ import annotations

from typing import Sequence

import jax
import jax.numpy as jnp

from repro.obs import get_metrics, get_tracer

from .batching import bucket_batch, round_up_multiple
from .kmeans import kmeans_batched
from .nmfk import nmfk_score_batched, nmfk_score_sharded

Array = jax.Array


def _place(x: Array, mesh, *spec) -> Array:
    """``x`` laid out as ``PartitionSpec(*spec)`` over ``mesh`` (no-op
    without a mesh), so that sharded dispatches read arrays already spread
    over the mesh instead of copying them from the first device each call."""
    if mesh is None:
        return x
    from jax.sharding import NamedSharding, PartitionSpec

    return jax.device_put(x, NamedSharding(mesh, PartitionSpec(*spec)))


class _BatchPlaneBase:
    """Shared padding / bucketing / accounting for the batched planes."""

    def __init__(
        self,
        k_pad: int | None,
        pad_batch: bool,
        mesh=None,
        lane_axis: str = "lane",
        data_axis: str = "data",
        bucket_min: int | None = None,
        comm: str = "sync",
    ):
        from .distributed import COMM_MODES, auto_mesh

        if comm not in COMM_MODES:
            raise ValueError(f"comm must be one of {COMM_MODES}, got {comm!r}")
        self.k_pad = k_pad
        self.pad_batch = pad_batch
        self.mesh = None if mesh is None else auto_mesh(mesh)
        self.comm = comm
        self.lane_axis = lane_axis
        self.data_axis = data_axis
        shape = dict(mesh.shape) if mesh is not None else {}
        if mesh is not None and lane_axis not in shape:
            raise ValueError(f"mesh {mesh} has no {lane_axis!r} axis")
        self.lane_count = shape.get(lane_axis, 1)
        self.data_count = shape.get(data_axis, 1)
        # pow2 floor: pad small waves up to one full lane sweep so every
        # wave size below the lane count shares a single compiled shape
        self.bucket_min = bucket_min if bucket_min is not None else max(self.lane_count, 1)
        # dispatch cap (number of lanes per batch). WavefrontScheduler sets
        # this to its max_wave so batch padding never exceeds the
        # device-memory bound the cap was chosen for.
        self.dispatch_cap: int | None = None
        self.n_dispatches = 0
        self.n_evals = 0
        self.shapes_compiled: set[tuple[int, int]] = set()
        self.last_lane_utilization: float | None = None

    # -- padding ----------------------------------------------------------------
    def _pad_ks(self, ks: Sequence[int]) -> tuple[list[int], int, int]:
        ks = [int(k) for k in ks]
        if not ks:
            raise ValueError("evaluate_batch needs at least one k")
        k_pad = self.k_pad if self.k_pad is not None else max(ks)
        if k_pad < max(ks):
            raise ValueError(f"plane k_pad={k_pad} smaller than requested k={max(ks)}")
        n_real = len(ks)
        if self.pad_batch:
            target = bucket_batch(
                n_real,
                lanes=self.lane_count,
                bucket_min=self.bucket_min,
                cap=self.dispatch_cap,
                compiled=(b for b, kp in self.shapes_compiled if kp == k_pad),
            )
        elif self.lane_count > 1:
            # no pow2 bucketing, but a sharded dispatch must still split
            # evenly over the mesh's lane axis
            target = round_up_multiple(n_real, self.lane_count)
        else:
            target = n_real
        ks = ks + [ks[0]] * (target - n_real)
        self.n_dispatches += 1
        self.n_evals += n_real
        util = n_real / len(ks)
        self.last_lane_utilization = util
        get_metrics().observe("lane_utilization", util)
        shape = (len(ks), k_pad)
        if shape not in self.shapes_compiled:
            # new padded shape == a jit cache miss on the next dispatch: the
            # batched fits are compiled per (batch, k_pad), so recompiles
            # become visible in the trace instead of silent wall-clock.
            self.shapes_compiled.add(shape)
            get_metrics().inc("compile_count")
            get_tracer().event(
                "compile", track=self._dispatch_track(), batch=shape[0], k_pad=shape[1],
                lanes=self.lane_count, data=self.data_count,
            )
        return ks, k_pad, n_real

    # -- telemetry ---------------------------------------------------------------
    def _dispatch_track(self) -> str:
        return "device:all" if self.mesh is not None else "device:0"

    def _emit_lane_spans(
        self, tracer, t0_us: float, padded: list[int], n_real: int, kind: str
    ) -> None:
        """Retroactive per-device-group spans: lane group i carried the
        contiguous slice padded[i*per:(i+1)*per] for the whole dispatch."""
        if self.mesh is None or self.lane_count <= 1 or not tracer.enabled:
            return
        dur = max(tracer.now_us() - t0_us, 0.0)
        per = len(padded) // self.lane_count
        for i in range(self.lane_count):
            lane_ks = padded[i * per : (i + 1) * per]
            real = max(0, min(n_real - i * per, per))
            tracer.add_span(
                "lane", t0_us, dur, track=f"device:{i}",
                kind=kind, ks=lane_ks, n_real=real, data_shards=self.data_count,
            )

    # chunk size of the abortable scalar path; per-chunk sweep counts land
    # in ``last_scalar_sweeps`` (the abort regression test's probe)
    abort_chunk = 25
    last_scalar_sweeps: int | None = None

    def evaluate_one(self, k: int, should_abort=None) -> float:
        # Without an abort callback: one fused dispatch (bucketing reuses
        # the nearest already-compiled (batch, k_pad) shape rather than
        # compiling a batch-of-one executable). With one, route through the
        # subclass's chunked scalar path so §III-D prunes landing mid-fit
        # actually stop the sweeps — the batched planes used to discard the
        # callback entirely.
        if should_abort is not None:
            return self._evaluate_one_chunked(k, should_abort)
        return self.evaluate_batch([k])[0]

    def _evaluate_one_chunked(self, k: int, should_abort) -> float:
        # fallback for planes without a resumable fit: poll once up front
        # (a k pruned before dispatch costs nothing), then run the fused fit
        if should_abort():
            return float("nan")
        return self.evaluate_batch([k])[0]


class NMFkBatchPlane(_BatchPlaneBase):
    """NMFk stability scoring of a whole wave as one padded vmapped ensemble.

    Per-lane RNG is ``fold_in(key, k)`` — the same schedule as
    ``make_nmfk_evaluator`` — so the batched and threaded executors agree
    on the score landscape (exactly at k == k_pad, to init-draw noise
    below it).

    With ``mesh=`` the ensemble is shard_map'd: k-lanes split over the
    ``lane`` axis; if the mesh's ``data`` axis is non-trivial, V's rows are
    additionally sharded and each fit runs the distributed psum structure
    (requires ``v.shape[0]`` divisible by the data-axis size).
    ``comm="pipelined"`` switches those data-sharded fits to the
    decomposed-psum schedule that overlaps the Gram reductions with the
    local W-update; each such dispatch publishes an ``overlap_fraction``
    gauge and (when tracing) modeled per-sweep comm/compute spans.
    """

    def __init__(
        self,
        v: Array,
        key: Array,
        n_perturbs: int = 8,
        nmf_iters: int = 150,
        epsilon: float = 0.015,
        statistic: str = "min",
        k_pad: int | None = None,
        pad_batch: bool = True,
        use_kernel: bool = False,
        mesh=None,
        lane_axis: str = "lane",
        data_axis: str = "data",
        bucket_min: int | None = None,
        comm: str = "sync",
    ):
        super().__init__(k_pad, pad_batch, mesh, lane_axis, data_axis, bucket_min, comm)
        if statistic not in ("min", "mean"):
            raise ValueError(f"statistic must be 'min' or 'mean', got {statistic!r}")
        if self.data_count > 1 and v.shape[0] % self.data_count:
            raise ValueError(
                f"v rows {v.shape[0]} not divisible by data-axis size {self.data_count}"
            )
        self.v = _place(v, self.mesh, data_axis if self.data_count > 1 else None)
        self.key = key
        self.n_perturbs = n_perturbs
        self.nmf_iters = nmf_iters
        self.epsilon = epsilon
        self.statistic = statistic
        self.use_kernel = use_kernel

    def _score_wave(self, padded: Sequence[int], k_pad: int):
        if self.mesh is not None:
            return nmfk_score_sharded(
                self.v, padded, self.key, self.mesh,
                k_pad=k_pad, n_perturbs=self.n_perturbs, nmf_iters=self.nmf_iters,
                epsilon=self.epsilon, use_kernel=self.use_kernel,
                lane_axis=self.lane_axis, data_axis=self.data_axis, comm=self.comm,
            )
        return nmfk_score_batched(
            self.v, padded, self.key,
            k_pad=k_pad, n_perturbs=self.n_perturbs, nmf_iters=self.nmf_iters,
            epsilon=self.epsilon, use_kernel=self.use_kernel,
        )

    def _evaluate_one_chunked(self, k: int, should_abort) -> float:
        """Scalar NMFk with §III-D abort polling at chunk boundaries.

        Runs the k's perturbation ensemble as cold elastic lanes advanced
        ``abort_chunk`` sweeps per dispatch (draw-for-draw and
        sweep-for-sweep identical to the fused batch fit when it runs to
        completion — the elastic kernels share ``_masked_sweeps``). If the
        abort fires between chunks, the remaining sweeps are never paid and
        the partial ensemble is scored as-is: Binary Bleed pruned this k,
        so its score only matters for accounting, never for ``k_optimal``
        (pruning soundness). Aborts before the first chunk return NaN — a
        void score no threshold test selects. Single-device by design: the
        scalar path is the thread executor's, not the mesh's.
        """
        from .nmfk import (
            elastic_chunk,
            elastic_lane_init,
            elastic_lane_keys,
            elastic_pooled_score,
        )

        k = int(k)
        k_pad = self.k_pad if self.k_pad is not None else k
        P = self.n_perturbs
        kj = jnp.asarray(k)
        pkeys, fkeys = elastic_lane_keys(self.key, k, P)
        pairs = [
            elastic_lane_init(self.v, kj, pkeys[p], fkeys[p], k_pad, self.epsilon)
            for p in range(P)
        ]
        w = jnp.stack([p[0] for p in pairs])
        h = jnp.stack([p[1] for p in pairs])
        keff = jnp.full((P,), k, jnp.int32)
        done = 0
        errs = None
        self.last_scalar_sweeps = 0
        while done < self.nmf_iters:
            if should_abort():
                break
            step = min(self.abort_chunk, self.nmf_iters - done)
            steps = jnp.full((P,), step, jnp.int32)
            w, h, errs = elastic_chunk(
                self.v, w, h, keff, steps, pkeys, k_pad, self.abort_chunk,
                self.epsilon, use_kernel=self.use_kernel,
            )
            done += step
            self.last_scalar_sweeps = done * P
        if errs is None:
            return float("nan")
        sc = elastic_pooled_score(w, errs, kj, k_pad, P, self.use_kernel)
        return float(sc.min_silhouette if self.statistic == "min" else sc.mean_silhouette)

    _MAX_TRACE_SWEEPS = 16  # per-sweep modeled spans emitted per dispatch

    def _emit_overlap_telemetry(self, tracer, t0_us: float, k_pad: int) -> None:
        """Publish the pipelined schedule's comm/compute overlap.

        The sweeps live inside one jit'd fori_loop, so per-sweep timing is
        not host-observable; spans are *modeled* — the measured dispatch
        wall time apportioned uniformly over sweeps, comm span lengths from
        ``overlap_model`` — and marked as such. The ``overlap_fraction``
        gauge (share of per-sweep comm hidden behind the local W-update) is
        always published; spans only when tracing is on.
        """
        if self.comm != "pipelined" or self.data_count <= 1:
            return
        from .distributed import overlap_model

        model = overlap_model(self.v.shape[0], self.v.shape[1], k_pad, self.data_count)
        get_metrics().set_gauge("overlap_fraction", model["overlap_fraction"])
        if not tracer.enabled:
            return
        dur = max(tracer.now_us() - t0_us, 0.0)
        sweeps = min(self.nmf_iters, self._MAX_TRACE_SWEEPS)
        per = dur / max(self.nmf_iters, 1)
        comm_dur = per * model["comm_fraction"]
        for i in range(sweeps):
            t = t0_us + i * per
            tracer.add_span(
                "sweep_compute", t, per, track="data:compute",
                sweep=i, modeled=True, data_shards=self.data_count,
            )
            tracer.add_span(
                "gram_ring", t, comm_dur, track="data:comm",
                sweep=i, modeled=True,
                overlap_fraction=model["overlap_fraction"],
            )

    def evaluate_batch(self, ks: Sequence[int]) -> list[float]:
        tracer = get_tracer()
        padded, k_pad, n_real = self._pad_ks(ks)
        t0_us = tracer.now_us()
        # "fit" brackets the fused fit+score dispatch (one jit'd ensemble);
        # "score" brackets device->host sync of the silhouette statistics.
        with tracer.span("fit", track=self._dispatch_track(), kind="nmfk",
                         ks=[int(k) for k in ks], batch=len(padded), k_pad=k_pad,
                         comm=self.comm):
            sc = self._score_wave(padded, k_pad)
            scores = sc.min_silhouette if self.statistic == "min" else sc.mean_silhouette
        with tracer.span("score", track=self._dispatch_track(), kind="nmfk", batch=len(padded)):
            out = [float(s) for s in scores[:n_real]]
        self._emit_lane_spans(tracer, t0_us, padded, n_real, kind="nmfk")
        self._emit_overlap_telemetry(tracer, t0_us, k_pad)
        return out


class KMeansBatchPlane(_BatchPlaneBase):
    """K-Means Davies-Bouldin (minimize) or silhouette (maximize) per wave.

    Lane i reproduces ``kmeans(x, ks[i], fold_in(key, ks[i]))`` exactly
    (masked fits are draw-for-draw identical to per-k fits), so this plane
    matches a threaded K-Means evaluator score-for-score.

    ``mesh=`` shards the wave's k axis over the mesh's ``lane`` axis; the
    data matrix stays replicated (K-Means assignment has no pyDNMFk-style
    Gram psum structure to reuse — a data axis of size > 1 is rejected).
    ``comm`` is accepted for executor-matrix uniformity but is a no-op:
    a lane-only dispatch has no cross-shard collectives to pipeline, so
    ``"pipelined"`` is bit-identical to ``"sync"`` here.
    """

    def __init__(
        self,
        x: Array,
        key: Array,
        score: str = "davies_bouldin",
        max_iters: int = 100,
        k_pad: int | None = None,
        pad_batch: bool = True,
        use_kernel: bool = False,
        mesh=None,
        lane_axis: str = "lane",
        data_axis: str = "data",
        bucket_min: int | None = None,
        comm: str = "sync",
    ):
        super().__init__(k_pad, pad_batch, mesh, lane_axis, data_axis, bucket_min, comm)
        if score not in ("davies_bouldin", "silhouette"):
            raise ValueError(f"score must be 'davies_bouldin' or 'silhouette', got {score!r}")
        if self.data_count > 1:
            raise ValueError("KMeansBatchPlane supports lane-only meshes (data axis must be 1)")
        self.x = x
        self.key = key
        self.score = score
        self.max_iters = max_iters
        self.use_kernel = use_kernel
        self._sharded_fns: dict[int, object] = {}

    def _sharded_fn(self, k_pad: int):
        """Jitted shard_map'd fit+score for this plane's mesh (per k_pad)."""
        fn = self._sharded_fns.get(k_pad)
        if fn is not None:
            return fn
        from jax.sharding import PartitionSpec as P

        from repro.core.scoring import davies_bouldin_score_masked, silhouette_score_masked

        from .kmeans import _kmeans_masked

        score, max_iters, use_kernel = self.score, self.max_iters, self.use_kernel
        lane = self.lane_axis

        def body(ks_l, keys_l, x):
            res = jax.vmap(
                lambda k_eff, sub: _kmeans_masked(x, k_eff, sub, k_pad, max_iters)
            )(ks_l, keys_l)
            if score == "davies_bouldin":
                cluster_mask = jnp.arange(k_pad)[None, :] < ks_l[:, None]
                return davies_bouldin_score_masked(
                    x, res.labels, k_pad, cluster_mask=cluster_mask
                )
            return silhouette_score_masked(x, res.labels, k_pad, use_kernel=use_kernel)

        fn = jax.jit(jax.shard_map(
            body, mesh=self.mesh,
            in_specs=(P(lane), P(lane, None), P()),
            out_specs=P(lane),
            check_vma=False,  # scores replicated only over trivial axes; RNG defeats inference
        ))
        self._sharded_fns[k_pad] = fn
        return fn

    def _evaluate_one_chunked(self, k: int, should_abort) -> float:
        """Scalar K-Means with abort polling between Lloyd chunks.

        Chunking is bitwise-free here: the resumable ``_kmeans_masked_chunk``
        halts on exactly the convergence condition the fused while_loop
        uses, so an unaborted chunked fit reproduces the batch fit's
        centroids; the host stops early when delta clears tol. Aborts
        before the first chunk return NaN (void score).
        """
        from repro.core.scoring import davies_bouldin_score_masked, silhouette_score_masked

        from .kmeans import (
            _kmeans_masked_assign,
            _kmeans_masked_chunk,
            _kmeans_masked_init,
        )

        k = int(k)
        k_pad = self.k_pad if self.k_pad is not None else k
        sub = jax.random.fold_in(self.key, k)
        kj = jnp.asarray(k)
        centers = _kmeans_masked_init(self.x, kj, sub, k_pad)
        it = 0
        ran = False
        self.last_scalar_sweeps = 0
        while it < self.max_iters:
            if should_abort():
                break
            chunk = min(self.abort_chunk, self.max_iters - it)
            centers, delta, did = _kmeans_masked_chunk(self.x, centers, kj, k_pad, chunk)
            it += int(did)
            ran = True
            self.last_scalar_sweeps = it
            if float(delta) <= 1e-6:
                break
        if not ran:
            return float("nan")
        labels, _ = _kmeans_masked_assign(self.x, centers, kj, k_pad)
        if self.score == "davies_bouldin":
            cluster_mask = (jnp.arange(k_pad) < kj)[None, :]
            scores = davies_bouldin_score_masked(
                self.x, labels[None], k_pad, cluster_mask=cluster_mask
            )
        else:
            scores = silhouette_score_masked(
                self.x, labels[None], k_pad, use_kernel=self.use_kernel
            )
        return float(scores[0])

    def evaluate_batch(self, ks: Sequence[int]) -> list[float]:
        from repro.core.scoring import davies_bouldin_score_masked, silhouette_score_masked

        from .batching import batched_lanes

        tracer = get_tracer()
        padded, k_pad, n_real = self._pad_ks(ks)
        t0_us = tracer.now_us()
        if self.mesh is not None:
            with tracer.span("fit", track=self._dispatch_track(), kind="kmeans",
                             ks=[int(k) for k in ks], batch=len(padded), k_pad=k_pad):
                ks_arr, keys, k_pad = batched_lanes(padded, self.key, k_pad)
                scores = self._sharded_fn(k_pad)(ks_arr, keys, self.x)
            with tracer.span("score", track=self._dispatch_track(), kind=self.score,
                             batch=len(padded)):
                out = [float(s) for s in scores[:n_real]]
            self._emit_lane_spans(tracer, t0_us, padded, n_real, kind="kmeans")
            return out
        with tracer.span("fit", track=self._dispatch_track(), kind="kmeans",
                         ks=[int(k) for k in ks], batch=len(padded), k_pad=k_pad):
            res = kmeans_batched(self.x, padded, self.key, k_pad=k_pad, max_iters=self.max_iters)
        ks_arr = jnp.asarray(padded)
        cluster_mask = jnp.arange(k_pad)[None, :] < ks_arr[:, None]  # (b, k_pad)
        # x stays unbatched (n, d): the jnp scorer tiers broadcast it against
        # the batched labels so the point-pairwise work is done once, while
        # the Pallas tier streams per-lane tiles that never hit HBM.
        with tracer.span("score", track=self._dispatch_track(), kind=self.score,
                         batch=len(padded)):
            if self.score == "davies_bouldin":
                scores = davies_bouldin_score_masked(
                    self.x, res.labels, k_pad, cluster_mask=cluster_mask
                )
            else:
                scores = silhouette_score_masked(
                    self.x, res.labels, k_pad, use_kernel=self.use_kernel
                )
            return [float(s) for s in scores[:n_real]]


# ---------------------------------------------------------------------------
# elastic plane: continuous batching of (k, perturbation) fit-chunks
# ---------------------------------------------------------------------------
import dataclasses
import functools
from collections import deque

import numpy as np


def _pool_compact(w, h, keff, pkeys, perm, w_new=None, h_new=None):
    """The slot pool after a tick: ``w_new``/``h_new`` (a chunk's output,
    if any) written over its ``[0:batch]`` prefix, then every array gathered
    by ``perm`` along the slot axis (new slot i reads old slot ``perm[i]``).
    Also returns the chunk's W rows, each its own buffer, so a row kept on
    the host outlives the pool it came from."""
    rows = ()
    if w_new is not None:
        batch = w_new.shape[0]
        w = w.at[:batch].set(w_new)
        h = h.at[:batch].set(h_new)
        rows = tuple(w_new[i] for i in range(batch))
    return (w[perm], h[perm], keff[perm], pkeys[perm]), rows


@functools.lru_cache(maxsize=64)
def _pool_compact_fn(shardings):
    """``_pool_compact`` jitted with the pool donated and kept where it lies:
    ``shardings`` holds each pool array's own sharding, or None for one
    that no placement committed (left to follow its inputs, as eager ops
    would), so a mesh's shard_map'd chunk reads the pool in place and
    donation can reuse its buffers. Compiles once per (batch, k_pad, slots),
    as the chunk program does, and once per slot count for a gather alone."""
    return jax.jit(_pool_compact, donate_argnums=(0, 1, 2, 3),
                   out_shardings=(shardings, None))


def _pool_shardings(pool) -> tuple:
    """The key of ``_pool_compact_fn`` for these pool arrays."""
    return tuple(x.sharding if x.committed else None for x in pool)


@dataclasses.dataclass
class _Lane:
    """One occupied slot: a single perturbation fit of a single k."""

    k: int
    p: int
    done: int = 0  # MU sweeps applied so far
    prev_err: float = float("inf")  # rel_error at the previous chunk boundary


@dataclasses.dataclass
class _KTask:
    """Host-side lifecycle of one submitted k (its P perturbation lanes)."""

    pkeys: Array  # (P, 2) perturbation-noise keys
    fkeys: Array  # (P, 2) init keys
    w_parts: dict = dataclasses.field(default_factory=dict)  # p -> (n, k_pad) W
    errs: dict = dataclasses.field(default_factory=dict)  # p -> final rel_error
    cancelled: bool = False
    scored: bool = False


class NMFkElasticPlane:
    """Convergence-gated chunked NMFk fits over a fixed pool of lane slots.

    The unit of dispatch is a *chunk* — ``chunk`` masked MU sweeps of every
    occupied lane, one jit'd vmapped (or shard_map'd) call at a fixed
    padded shape — instead of a whole wave of fixed-iteration fits. One
    lane is one (k, perturbation) fit. Between chunks, host-side:

      * **convergence gate** — a lane retires when its rel_error improved
        by less than ``tol`` over the last chunk (or its sweep budget
        ``nmf_iters`` is exhausted); the sweeps it didn't run are counted
        as ``sweeps_saved``;
      * **lane refill** — freed slots immediately drain queued
        (k, perturbation) lanes submitted by the scheduler, so the batch
        stays full while ks enter and leave at their own pace
        (continuous batching applied to the k-search);
      * **warm starts** — a refilled lane seeds its W from the nearest
        completed k's factors via ``elastic_lane_warm_init`` (column
        pad/truncate + re-normalize; cold ``nmf_init``-style draw when the
        ``WarmStartCache`` has nothing within its window);
      * **eviction** — ``cancel(k)`` (the scheduler's reaction to a Binary
        Bleed prune) removes queued lanes and evicts in-flight ones
        mid-fit, crediting their remaining sweeps to ``sweeps_saved`` —
        §III-D abort made first-class.

    ``tol <= 0`` disables the gate: every lane runs exactly ``nmf_iters``
    sweeps and (with ``warm_start=False``) reproduces the fixed-iteration
    batched plane draw-for-draw — the oracle the conformance tests tighten
    ``tol`` toward. Accounting invariant (checked by the elastic bench):
    ``sweeps_run + sweeps_saved == sweeps_fixed_total`` over any completed
    search, where ``sweeps_fixed_total`` counts ``n_perturbs * nmf_iters``
    for every submitted k.

    Occupied slots are kept compacted in a prefix (retirement swaps the
    last occupied lane into the freed slot), and each dispatch runs the
    bucketed prefix (``bucket_batch`` pow2 policy), so compiled shapes stay
    O(log slots). Per-lane sweep budgets ride the traced ``steps`` vector —
    a lane near its budget trims its final chunk inside the same compiled
    shape.
    """

    def __init__(
        self,
        v: Array,
        key: Array,
        n_perturbs: int = 8,
        nmf_iters: int = 150,
        epsilon: float = 0.015,
        statistic: str = "min",
        k_pad: int | None = None,
        tol: float = 1e-3,
        chunk: int = 25,
        slots: int | None = None,
        warm_start: bool = True,
        warm_window: int = 8,
        use_kernel: bool = False,
        mesh=None,
        lane_axis: str = "lane",
        data_axis: str = "data",
        comm: str = "sync",
    ):
        from .batching import WarmStartCache, next_pow2
        from .distributed import COMM_MODES, auto_mesh

        if statistic not in ("min", "mean"):
            raise ValueError(f"statistic must be 'min' or 'mean', got {statistic!r}")
        if comm not in COMM_MODES:
            raise ValueError(f"comm must be one of {COMM_MODES}, got {comm!r}")
        if k_pad is None:
            raise ValueError("NMFkElasticPlane needs an explicit k_pad (slots persist across ks)")
        if chunk < 1:
            raise ValueError("chunk must be >= 1")
        shape = dict(mesh.shape) if mesh is not None else {}
        if mesh is not None and lane_axis not in shape:
            raise ValueError(f"mesh {mesh} has no {lane_axis!r} axis")
        if mesh is not None:
            mesh = auto_mesh(mesh)
        self.lane_count = shape.get(lane_axis, 1)
        self.data_count = shape.get(data_axis, 1)
        if self.data_count > 1 and v.shape[0] % self.data_count:
            raise ValueError(
                f"v rows {v.shape[0]} not divisible by data-axis size {self.data_count}"
            )
        if slots is None:
            slots = round_up_multiple(next_pow2(max(2 * n_perturbs, self.lane_count)), self.lane_count)
        if slots < 1 or slots % max(self.lane_count, 1):
            raise ValueError(f"slots={slots} must be a positive multiple of lane count {self.lane_count}")
        # on a mesh, V and the slot pool live where the shard_map'd chunk
        # reads them: lanes over the lane axis, rows over the data axis
        row_axis = data_axis if self.data_count > 1 else None
        self.v = _place(v, mesh, row_axis)
        self.key = key
        self.n_perturbs = int(n_perturbs)
        self.nmf_iters = int(nmf_iters)
        self.epsilon = float(epsilon)
        self.statistic = statistic
        self.k_pad = int(k_pad)
        self.tol = float(tol)
        self.chunk = int(chunk)
        self.slots = int(slots)
        self.warm_start = bool(warm_start)
        self.use_kernel = bool(use_kernel)
        self.mesh = mesh
        self.lane_axis = lane_axis
        self.data_axis = data_axis
        self.comm = comm
        self.warm_cache = WarmStartCache(window=warm_window)

        n, m = v.shape
        self._w = _place(
            jnp.zeros((self.slots, n, self.k_pad), v.dtype), mesh, lane_axis, row_axis
        )
        self._h = _place(jnp.zeros((self.slots, self.k_pad, m), v.dtype), mesh, lane_axis)
        self._keff = _place(jnp.zeros((self.slots,), jnp.int32), mesh, lane_axis)
        self._pkeys = _place(jnp.zeros((self.slots, 2), jnp.uint32), mesh, lane_axis)
        self._slot: list[_Lane | None] = [None] * self.slots
        self._n_occ = 0
        # moves nothing: compiles the gather a cancel runs with the plane, so
        # that no cancel compiles mid-search
        self._compact_pool([])
        self._queue: deque[tuple[int, int]] = deque()
        self._tasks: dict[int, _KTask] = {}
        self._ready: list[tuple[int, float]] = []

        # accounting (the bench's invariant: run + saved == fixed_total)
        self.sweeps_run = 0
        self.sweeps_saved = 0
        self.sweeps_fixed_total = 0
        self.n_ticks = 0
        self.shapes_compiled: set[tuple[int, int]] = set()
        self.last_lane_occupancy: float | None = None

    # -- scheduler surface -------------------------------------------------------
    @property
    def pool(self) -> tuple[Array, Array, Array, Array]:
        """The slot pool's device buffers: (w, h, k_eff, pkeys)."""
        return self._w, self._h, self._keff, self._pkeys

    @property
    def backlog(self) -> int:
        """Queued lanes not yet slotted (admission signal for the refiller)."""
        return len(self._queue)

    @property
    def idle(self) -> bool:
        return not self._queue and self._n_occ == 0 and not self._ready

    def inflight_ks(self) -> set[int]:
        """ks submitted but not yet scored or cancelled."""
        return {
            k for k, t in self._tasks.items() if not t.scored and not t.cancelled
        }

    def submit(self, k: int) -> None:
        """Enqueue the P perturbation lanes of k (slotted by the next tick)."""
        from .nmfk import elastic_lane_keys

        k = int(k)
        if k > self.k_pad:
            raise ValueError(f"k={k} exceeds plane k_pad={self.k_pad}")
        if k in self._tasks:
            raise ValueError(f"k={k} already submitted")
        pkeys, fkeys = elastic_lane_keys(self.key, k, self.n_perturbs)
        self._tasks[k] = _KTask(pkeys=pkeys, fkeys=fkeys)
        for p in range(self.n_perturbs):
            self._queue.append((k, p))
        self.sweeps_fixed_total += self.n_perturbs * self.nmf_iters
        get_metrics().inc("sweeps_fixed_total", self.n_perturbs * self.nmf_iters)

    def cancel(self, k: int) -> bool:
        """Evict k mid-flight (Binary Bleed pruned it): dequeue its pending
        lanes and free its occupied slots, crediting unspent sweeps."""
        k = int(k)
        task = self._tasks.get(k)
        if task is None or task.scored or task.cancelled:
            return False
        task.cancelled = True
        pending = sum(1 for kk, _ in self._queue if kk == k)
        if pending:
            self._queue = deque((kk, p) for kk, p in self._queue if kk != k)
            self._credit_saved(pending * self.nmf_iters)
        freed = [i for i in range(self._n_occ - 1, -1, -1) if self._slot[i].k == k]
        for i in freed:
            self._credit_saved(self.nmf_iters - self._slot[i].done)
        evicted = len(freed)
        if freed:
            self._compact_pool(freed)
        get_tracer().event("evict", track=self._dispatch_track(), k=k,
                           pending=pending, evicted=evicted)
        return True

    def tick(self) -> list[tuple[int, float]]:
        """Refill freed slots, advance every occupied lane one chunk, retire
        converged / budget-exhausted lanes; returns newly scored (k, score).

        Under an enabled tracer the phases are spans: ``refill``, ``chunk``
        (holding ``readback``, the blocking read of the lane errors) and
        ``retire`` (holding one ``score`` per scored k). ``host_syncs``
        counts the tick's blocking device-to-host reads: one per occupied
        lane's error, one per scored k.
        """
        tracer = get_tracer()
        metrics = get_metrics()
        with tracer.span("refill", track="wavefront") as refill:
            warm, cold = self._refill()
            if tracer.enabled:
                refill.set(slotted=warm + cold, warm=warm, cold=cold)
        if self._n_occ == 0:
            out, self._ready = self._ready, []
            return out
        self.n_ticks += 1
        n_occ = self._n_occ
        batch = bucket_batch(
            n_occ, lanes=self.lane_count, bucket_min=min(self.lane_count, self.slots),
            cap=self.slots,
            compiled=(b for b, kp in self.shapes_compiled if kp == self.k_pad),
        )
        shape = (batch, self.k_pad)
        if shape not in self.shapes_compiled:
            self.shapes_compiled.add(shape)
            metrics.inc("compile_count")
            tracer.event("compile", track=self._dispatch_track(), batch=batch,
                         k_pad=self.k_pad, lanes=self.lane_count, data=self.data_count)
        steps_host = [
            min(self.chunk, self.nmf_iters - self._slot[i].done) if i < n_occ else 0
            for i in range(batch)
        ]
        occupancy = n_occ / batch
        self.last_lane_occupancy = occupancy
        metrics.observe("lane_occupancy", occupancy)
        track = self._dispatch_track()
        attrs = self._chunk_attrs(batch, n_occ, steps_host) if tracer.enabled else {}
        with tracer.span("chunk", track=track, **attrs):
            w_new, h_new, errs = self._dispatch(batch, jnp.asarray(steps_host, jnp.int32))
            with tracer.span("readback", track=track):
                errs_host = [float(e) for e in errs[:n_occ]]

        with tracer.span("retire", track="wavefront"):
            swept = sum(steps_host)
            self.sweeps_run += swept
            metrics.inc("sweeps_run", swept)
            retire: list[int] = []
            for i in range(n_occ):
                lane = self._slot[i]
                lane.done += steps_host[i]
                err = errs_host[i]
                converged = self.tol > 0 and (lane.prev_err - err) < self.tol
                lane.prev_err = err
                if converged or lane.done >= self.nmf_iters:
                    if lane.done < self.nmf_iters:
                        self._credit_saved(self.nmf_iters - lane.done)
                    retire.append(i)
            freed = sorted(retire, reverse=True)
            lanes = [self._slot[i] for i in freed]
            rows = self._compact_pool(freed, w_new, h_new)
            for i, lane in zip(freed, lanes):
                self._finish_lane(lane, rows[i], errs_host[i])
        out, self._ready = self._ready, []
        metrics.inc("host_syncs", n_occ + len(out))
        return out

    # -- internals ---------------------------------------------------------------
    def _dispatch_track(self) -> str:
        return "device:all" if self.mesh is not None else "device:0"

    def _chunk_attrs(self, batch: int, n_occ: int, steps_host: list[int]) -> dict:
        """The ``chunk`` span's attributes: the dispatch's shape, its largest
        sweep count and distinct ranks, and each occupied lane's k and sweeps."""
        lane_ks = [self._slot[i].k for i in range(n_occ)]
        return dict(
            kind="nmfk_elastic", batch=batch, n_occ=n_occ, k_pad=self.k_pad,
            sweeps=max(steps_host), ks=sorted(set(lane_ks)), lane_ks=lane_ks,
            lane_steps=steps_host[:n_occ],
        )

    def _credit_saved(self, sweeps: int) -> None:
        if sweeps > 0:
            self.sweeps_saved += sweeps
            get_metrics().inc("sweeps_saved", sweeps)

    def _dispatch(self, batch: int, steps: Array):
        from .nmfk import elastic_chunk, elastic_chunk_sharded

        w, h = self._w[:batch], self._h[:batch]
        keff, pkeys = self._keff[:batch], self._pkeys[:batch]
        if self.mesh is not None:
            return elastic_chunk_sharded(
                self.v, w, h, keff, steps, pkeys, self.mesh, self.k_pad, self.chunk,
                self.epsilon, use_kernel=self.use_kernel, lane_axis=self.lane_axis,
                data_axis=self.data_axis, comm=self.comm,
            )
        return elastic_chunk(
            self.v, w, h, keff, steps, pkeys, self.k_pad, self.chunk, self.epsilon,
            use_kernel=self.use_kernel,
        )

    def _refill(self) -> tuple[int, int]:
        """Slot queued lanes into free slots; returns (warm, cold) lanes slotted."""
        from .nmfk import elastic_lane_init, elastic_lane_warm_init

        metrics = get_metrics()
        warm = cold = 0
        while self._queue and self._n_occ < self.slots:
            k, p = self._queue.popleft()
            task = self._tasks[k]
            if task.cancelled:  # defensive: cancel() already dequeues
                continue
            kj = jnp.asarray(k)
            src = self.warm_cache.nearest(k, p) if self.warm_start else None
            if src is not None:
                k_src, w_src = src
                w0, h0 = elastic_lane_warm_init(
                    self.v, kj, task.pkeys[p], task.fkeys[p], w_src,
                    jnp.asarray(k_src), self.k_pad, self.epsilon,
                )
                metrics.inc("warm_start_hits")
                get_tracer().event("warm_start", track=self._dispatch_track(),
                                   k=k, p=p, k_src=int(k_src))
                warm += 1
            else:
                w0, h0 = elastic_lane_init(
                    self.v, kj, task.pkeys[p], task.fkeys[p], self.k_pad, self.epsilon
                )
                cold += 1
            i = self._n_occ
            self._w = self._w.at[i].set(w0)
            self._h = self._h.at[i].set(h0)
            self._keff = self._keff.at[i].set(k)
            self._pkeys = self._pkeys.at[i].set(task.pkeys[p])
            self._slot[i] = _Lane(k=k, p=p)
            self._n_occ += 1
        return warm, cold

    def _compact_pool(self, freed: list[int], w_new=None, h_new=None) -> tuple:
        """Free slots ``freed`` (descending) and keep the occupied slots a
        prefix: each freed slot takes the last occupied lane. The moves are
        replayed on the host's lane list into a source index per slot, and
        the device pool follows in one donated call that also writes a
        chunk's ``w_new``/``h_new`` over its prefix; returns that chunk's W
        rows. ``pool_moves`` counts the lanes moved."""
        perm = np.arange(self.slots, dtype=np.int32)
        moves = 0
        for i in freed:
            j = self._n_occ - 1
            if i != j:
                perm[i] = perm[j]
                self._slot[i] = self._slot[j]
                moves += 1
            self._slot[j] = None
            self._n_occ = j
        get_metrics().inc("pool_moves", moves)
        fn = _pool_compact_fn(_pool_shardings(self.pool))
        pool, rows = fn(*self.pool, perm, w_new, h_new)
        self._w, self._h, self._keff, self._pkeys = pool
        return rows

    def _finish_lane(self, lane: _Lane, w_row: Array, err: float) -> None:
        from .nmfk import elastic_pooled_score

        task = self._tasks[lane.k]
        task.w_parts[lane.p] = w_row
        task.errs[lane.p] = err
        self.warm_cache.put(lane.k, lane.p, w_row)
        if len(task.w_parts) < self.n_perturbs or task.cancelled:
            return
        with get_tracer().span("score", track="wavefront", k=lane.k):
            w_all = jnp.stack([task.w_parts[p] for p in range(self.n_perturbs)])
            errs = jnp.asarray(
                [task.errs[p] for p in range(self.n_perturbs)], self.v.dtype
            )
            sc = elastic_pooled_score(
                w_all, errs, jnp.asarray(lane.k), self.k_pad, self.n_perturbs,
                self.use_kernel,
            )
            score = float(sc.min_silhouette if self.statistic == "min" else sc.mean_silhouette)
        task.scored = True
        task.w_parts.clear()  # the warm cache holds what future ks need
        self._ready.append((lane.k, score))


__all__ = ["NMFkBatchPlane", "KMeansBatchPlane", "NMFkElasticPlane"]
