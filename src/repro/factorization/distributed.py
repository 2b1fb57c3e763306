"""Distributed NMF / RESCAL via shard_map — the paper's pyDNMFk/pyDRESCALk.

The paper's *distributed* mode: one k evaluation is too big for a node
(50 TB matrices, 52k cores), so the factorization itself is sharded. The
MPI communication structure of pyDNMFk maps 1:1 onto jax.lax collectives:

    V row-sharded over the mesh axis; W row-sharded; H replicated.
      H-update:  psum(W_l^T V_l) (k×m),  psum(W_l^T W_l) (k×k)
      W-update:  purely local (H replicated ⇒ H H^T local)

Gram-matrix psums are k×{m,k} — tiny next to V — so the algorithm is
compute-bound and scales like the paper's 52k-core runs. RESCAL adds an
all-gather of the entity factor A (n×k) per sweep.

Two communication schedules for the MU sweeps (``comm=``):

  * ``"sync"`` — each sweep blocks on the two Gram all-reduces before any
    factor update (the textbook pyDNMFk order).
  * ``"pipelined"`` — each psum is decomposed into ``psum_scatter`` + ring
    ``all_gather`` (``ring_psum``), both Grams fused into one buffer so one
    collective pair is in flight per sweep, and the purely-local W-update
    runs with a **one-sweep-stale H** while the reduction is in transit.
    The W-update has no data dependency on the in-flight Grams, so XLA's
    async-collective scheduler overlaps communication with compute; a
    final synchronous sweep restores the coupled update before the
    residual is measured. Numerics differ from ``"sync"`` by the staleness
    (rel_error agreement ~5e-2 on small problems, see the conformance
    suite); total sweep count is identical.

These functions are shard_map'd under a caller-provided mesh: a Binary
Bleed "resource" hands us its sub-mesh, giving the paper's
parallel-over-k × distributed-within-k composition.
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.sharding import AxisType, Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

COMM_MODES = ("sync", "pipelined")

Array = jax.Array
_EPS = 1e-9


# ---------------------------------------------------------------------------
# ring collectives: psum decomposed into scatter + gather
# ---------------------------------------------------------------------------
def ring_all_gather(x: Array, axis: str, axis_size: int, use_ppermute: bool = False) -> Array:
    """All-gather ``x`` (a per-device chunk) along ``axis``.

    ``use_ppermute=True`` spells the gather as an explicit (axis_size - 1)-step
    ``ppermute`` ring — the schedule pyDNMFk's custom communicators build by
    hand, and the form whose per-step transfers interleave with compute on
    hardware rings. The default lowers to ``lax.all_gather`` and lets XLA
    pick the ring; both produce identical values.
    """
    if axis_size == 1:
        return x
    if not use_ppermute:
        return jax.lax.all_gather(x, axis, tiled=True)
    idx = jax.lax.axis_index(axis)
    chunk = x.shape[0]
    out = jnp.zeros((axis_size * chunk,) + x.shape[1:], x.dtype)
    out = jax.lax.dynamic_update_slice_in_dim(out, x, idx * chunk, axis=0)
    buf = x
    perm = [(i, (i + 1) % axis_size) for i in range(axis_size)]
    for step in range(1, axis_size):
        buf = jax.lax.ppermute(buf, axis, perm)
        src = (idx - step) % axis_size
        out = jax.lax.dynamic_update_slice_in_dim(out, buf, src * chunk, axis=0)
    return out


def ring_psum_start(x: Array, axis: str, axis_size: int) -> tuple[Array, int]:
    """First half of a decomposed psum: reduce-scatter ``x`` over ``axis``.

    Pads the leading dim to a multiple of ``axis_size`` (Gram matrices are
    k_pad-leading; k_pad need not divide the shard count) and returns the
    per-device reduced chunk plus the original leading extent. Everything
    between ``ring_psum_start`` and ``ring_psum_finish`` has no data
    dependency on the reduction, so the scheduler can run it while the
    collective is in flight.
    """
    if axis_size == 1:
        return x, x.shape[0]
    lead = x.shape[0]
    pad = (-lead) % axis_size
    if pad:
        x = jnp.concatenate([x, jnp.zeros((pad,) + x.shape[1:], x.dtype)], axis=0)
    shard = jax.lax.psum_scatter(x, axis, scatter_dimension=0, tiled=True)
    return shard, lead


def ring_psum_finish(
    shard: Array, lead: int, axis: str, axis_size: int, use_ppermute: bool = False
) -> Array:
    """Second half of a decomposed psum: gather the reduced chunks."""
    if axis_size == 1:
        return shard
    full = ring_all_gather(shard, axis, axis_size, use_ppermute=use_ppermute)
    return full[:lead] if full.shape[0] != lead else full


def ring_psum(x: Array, axis: str, axis_size: int, use_ppermute: bool = False) -> Array:
    """``lax.psum`` decomposed into ``psum_scatter`` + ring all-gather.

    Identical result up to float reduction order; the two-phase form is
    what the pipelined MU schedule interleaves compute into.
    """
    shard, lead = ring_psum_start(x, axis, axis_size)
    return ring_psum_finish(shard, lead, axis, axis_size, use_ppermute=use_ppermute)


def overlap_model(
    n_total: int,
    m: int,
    k_pad: int,
    data: int,
    machine_balance: float = 8.0,
) -> dict:
    """Analytic comm/compute model of one pipelined MU sweep per device.

    The ring moves ``2 (p-1)/p`` of the fused Gram buffer (reduce-scatter +
    all-gather) while the local stale-H W-update runs; ``machine_balance``
    converts moved elements into flop-equivalents (flops the machine
    executes in the time one element crosses the interconnect — a roofline
    balance knob, default representative of a CPU/Ethernet-class ratio;
    TPU-class fabrics are lower, hiding comm even more easily).

    Returns ``overlap_fraction`` (share of comm hidden behind the W-update),
    ``comm_fraction`` (comm share of the *sync* sweep), and the modeled
    pipelined-vs-sync ``speedup``. All quantities are per sweep; with
    ``data == 1`` there is no communication and every field degenerates to
    the no-op values.
    """
    if data <= 1:
        return {
            "overlap_fraction": 0.0,
            "comm_fraction": 0.0,
            "speedup": 1.0,
            "comm_flop_equiv": 0.0,
            "local_flops": 0.0,
        }
    n_l = n_total / data
    gram_elems = k_pad * (m + k_pad)
    comm_elems = 2.0 * (data - 1) / data * gram_elems
    comm_cost = comm_elems * machine_balance  # flop-equivalents
    # local work available to hide the in-flight ring: the W-update
    w_update_flops = 2.0 * n_l * m * k_pad + 2.0 * k_pad * k_pad * (m + n_l)
    # rest of the sweep: Gram products + H-update
    gram_flops = 2.0 * n_l * (m + k_pad) * k_pad
    h_update_flops = 2.0 * k_pad * k_pad * m
    compute = w_update_flops + gram_flops + h_update_flops
    overlap = min(w_update_flops, comm_cost) / comm_cost
    t_sync = compute + comm_cost
    t_pipe = compute + comm_cost * (1.0 - overlap)
    return {
        "overlap_fraction": overlap,
        "comm_fraction": comm_cost / t_sync,
        "speedup": t_sync / t_pipe,
        "comm_flop_equiv": comm_cost,
        "local_flops": w_update_flops,
    }


class DistNMFResult(NamedTuple):
    w: Array  # (n, k) row-sharded
    h: Array  # (k, m) replicated
    rel_error: Array


def _mu_sweeps(
    v_l: Array,
    w_l: Array,
    h: Array,
    active: Array | None,
    iters: int,
    axis: str,
    comm: str,
    axis_size: int,
    steps: Array | None = None,
):
    """Run ``iters`` multiplicative-update sweeps under the chosen schedule.

    ``active`` is the (k_pad,) rank mask of the masked fits (None for the
    unmasked path). ``"sync"`` blocks both factor updates on the Gram
    psums; ``"pipelined"`` fuses the two Grams into one ``(k, m+k)`` buffer,
    reduce-scatters it, runs the local W-update with the previous sweep's
    H while the ring gather is in flight, then finishes the H-update — a
    one-sweep-stale schedule closed by one final synchronous sweep so the
    measured residual comes from a coupled (W, H) pair.

    ``steps`` (a traced scalar) gates sweeps per call inside the fixed
    ``iters``-shaped loop: sweep s applies only while ``s < steps`` — the
    elastic executor's per-lane remaining-budget gate. With ``steps <
    iters`` under ``"pipelined"`` the closing synchronous sweep is gated
    off too (the lane's last applied sweep is a stale-H pipe sweep); the
    elastic conformance tolerance for pipelined runs absorbs this.
    """
    if comm not in COMM_MODES:
        raise ValueError(f"comm must be one of {COMM_MODES}, got {comm!r}")
    m = v_l.shape[1]

    def mask_h(h):
        return h if active is None else h * active[:, None]

    def mask_w(w):
        return w if active is None else w * active[None, :]

    def sync_sweep(carry):
        w_l, h = carry
        wtv = jax.lax.psum(w_l.T @ v_l, axis)  # (k, m) — the pyDNMFk all-reduce
        wtw = jax.lax.psum(w_l.T @ w_l, axis)  # (k, k)
        h = mask_h(h * wtv / (wtw @ h + _EPS))
        hht = h @ h.T  # local: H replicated
        w_l = mask_w(w_l * (v_l @ h.T) / (w_l @ hht + _EPS))
        return w_l, h

    def pipe_sweep(carry):
        w_l, h = carry
        # fused Gram: one scatter+gather pair in flight instead of two psums
        gram = w_l.T @ jnp.concatenate([v_l, w_l], axis=1)  # (k, m + k)
        shard, lead = ring_psum_start(gram, axis, axis_size)
        # ... overlapped: purely-local W-update with the stale (prev-sweep) H;
        # no data dependency on `shard`, so it hides the in-flight ring
        hht = h @ h.T
        w_new = mask_w(w_l * (v_l @ h.T) / (w_l @ hht + _EPS))
        # ... then complete the reduction and the H-update
        full = ring_psum_finish(shard, lead, axis, axis_size)
        wtv, wtw = full[:, :m], full[:, m:]
        h_new = mask_h(h * wtv / (wtw @ h + _EPS))
        return w_new, h_new

    def gated(s, carry, sweep):
        new = sweep(carry)
        if steps is None:
            return new
        live = s < steps
        return jnp.where(live, new[0], carry[0]), jnp.where(live, new[1], carry[1])

    if comm == "sync" or axis_size == 1 or iters == 0:
        return jax.lax.fori_loop(0, iters, lambda s, c: gated(s, c, sync_sweep), (w_l, h))
    w_l, h = jax.lax.fori_loop(0, iters - 1, lambda s, c: gated(s, c, pipe_sweep), (w_l, h))
    return gated(iters - 1, (w_l, h), sync_sweep)


def _dnmf_local(
    v_l: Array,
    key: Array,
    k: int,
    iters: int,
    axis: str,
    comm: str = "sync",
    axis_size: int = 1,
):
    """Per-shard NMF body. v_l: (n_local, m)."""
    n_l, m = v_l.shape
    idx = jax.lax.axis_index(axis)
    kw, kh = jax.random.split(key)
    # H must be bit-identical on every shard: same key everywhere.
    # W is local: fold in the shard index.
    v_mean = jax.lax.pmean(jnp.mean(v_l), axis)
    scale = jnp.sqrt(jnp.maximum(v_mean, _EPS) / k)
    w_l = scale * jax.random.uniform(jax.random.fold_in(kw, idx), (n_l, k), v_l.dtype, 0.1, 1.0)
    h = scale * jax.random.uniform(kh, (k, m), v_l.dtype, 0.1, 1.0)

    w_l, h = _mu_sweeps(v_l, w_l, h, None, iters, axis, comm, axis_size)
    sq = jnp.sum((v_l - w_l @ h) ** 2)
    vsq = jnp.sum(v_l**2)
    err = jnp.sqrt(jax.lax.psum(sq, axis) / jnp.maximum(jax.lax.psum(vsq, axis), _EPS))
    return w_l, h, err


def distributed_nmf(
    v: Array,
    k: int,
    key: Array,
    mesh: Mesh,
    iters: int = 200,
    axis: str = "data",
    comm: str = "sync",
) -> DistNMFResult:
    """Row-distributed NMF under `mesh` (v rows sharded over `axis`).

    ``comm="pipelined"`` overlaps the Gram reductions with the local
    W-update (one-sweep-stale H; see the module docstring).
    """
    axis_size = dict(mesh.shape)[axis]
    fn = jax.shard_map(
        functools.partial(
            _dnmf_local, k=k, iters=iters, axis=axis, comm=comm, axis_size=axis_size
        ),
        mesh=mesh,
        in_specs=(P(axis, None), P()),
        out_specs=(P(axis, None), P(), P()),
        # the ring gather's replication is invisible to rep inference
        check_vma=(comm == "sync" or axis_size == 1),
    )
    v = jax.device_put(v, NamedSharding(mesh, P(axis, None)))
    w, h, err = jax.jit(fn)(v, key)
    return DistNMFResult(w, h, err)


class DistRESCALResult(NamedTuple):
    a: Array  # (n, k) row-sharded
    r: Array  # (nr, k, k) replicated
    rel_error: Array


def _drescal_local(x_l: Array, key: Array, k: int, iters: int, axis: str):
    """Per-shard RESCAL body. x_l: (nr, n_local, n) — entity-row sharded."""
    nr, n_l, n = x_l.shape
    idx = jax.lax.axis_index(axis)
    ka, kr = jax.random.split(key)
    x_mean = jax.lax.pmean(jnp.mean(x_l), axis)
    scale = jnp.sqrt(jnp.maximum(x_mean, _EPS)) / k
    a_l = scale * jax.random.uniform(jax.random.fold_in(ka, idx), (n_l, k), x_l.dtype, 0.1, 1.0)
    r = scale * jax.random.uniform(kr, (nr, k, k), x_l.dtype, 0.1, 1.0)

    def body(_, carry):
        a_l, r = carry
        a_full = jax.lax.all_gather(a_l, axis, tiled=True)  # (n, k)
        ata = jax.lax.psum(a_l.T @ a_l, axis)  # (k, k)
        # A-update numerator, local rows:
        #   X_r A R_r^T  +  X_r^T A R_r   (row slice of the second term
        #   reconstructed from the local row block via psum)
        xar = jnp.einsum("rij,jl,rkl->ik", x_l, a_full, r)  # (n_l, k)
        xt_a_full = jax.lax.psum(
            jnp.einsum("rij,il->rjl", x_l, a_l), axis
        )  # (nr, n, k) = X_r^T A
        start = idx * n_l
        xt_a_l = jax.lax.dynamic_slice_in_dim(xt_a_full, start, n_l, axis=1)  # (nr, n_l, k)
        xar2 = jnp.einsum("rik,rkl->il", xt_a_l, r)  # X_r^T A R_r rows
        num = xar + xar2
        arat = jnp.einsum("rkl,lm,rnm->kn", r, ata, r)
        arat2 = jnp.einsum("rlk,lm,rmn->kn", r, ata, r)
        den = a_l @ (arat + arat2)
        a_l = a_l * num / (den + _EPS)
        # R-update
        ata = jax.lax.psum(a_l.T @ a_l, axis)
        a_full = jax.lax.all_gather(a_l, axis, tiled=True)
        atxa = jax.lax.psum(
            jnp.einsum("il,rij,jm->rlm", a_l, x_l, a_full), axis
        )  # (nr, k, k)
        den_r = jnp.einsum("ik,rkl,lj->rij", ata, r, ata)
        r = r * atxa / (den_r + _EPS)
        return a_l, r

    a_l, r = jax.lax.fori_loop(0, iters, body, (a_l, r))
    a_full = jax.lax.all_gather(a_l, axis, tiled=True)
    recon_l = jnp.einsum("ik,rkl,jl->rij", a_l, r, a_full)
    sq = jnp.sum((x_l - recon_l) ** 2)
    xsq = jnp.sum(x_l**2)
    err = jnp.sqrt(jax.lax.psum(sq, axis) / jnp.maximum(jax.lax.psum(xsq, axis), _EPS))
    return a_l, r, err


def distributed_rescal(
    x: Array,
    k: int,
    key: Array,
    mesh: Mesh,
    iters: int = 150,
    axis: str = "data",
) -> DistRESCALResult:
    """Entity-row-distributed RESCAL under `mesh`."""
    fn = jax.shard_map(
        functools.partial(_drescal_local, k=k, iters=iters, axis=axis),
        mesh=mesh,
        in_specs=(P(None, axis, None), P()),
        out_specs=(P(axis, None), P(), P()),
    )
    x = jax.device_put(x, NamedSharding(mesh, P(None, axis, None)))
    a, r, err = jax.jit(fn)(x, key)
    return DistRESCALResult(a, r, err)


def _dnmf_masked_local(
    v_l: Array,
    k_eff: Array,
    key: Array,
    k_pad: int,
    iters: int,
    axis: str,
    n_total: int,
    comm: str = "sync",
) -> tuple[Array, Array]:
    """Per-shard *masked* NMF body: ``_nmf_masked`` distributed over ``axis``.

    Same psum structure as ``_dnmf_local`` (H-update Gram matrices are the
    only collectives), but draw-compatible with the single-device masked
    fit: W and H are drawn full-shape from the replicated ``key`` exactly as
    ``_nmf_masked`` draws them, and each shard keeps only its row block of
    W. All cross-shard reductions are psums of k_pad×{m,k_pad} Grams, so
    with ``comm="sync"`` the result matches ``_nmf_masked(v, k_eff, key,
    k_pad, iters)`` up to float reduction order; ``comm="pipelined"``
    additionally carries the one-sweep-stale W-update schedule (see module
    docstring), trading exact sync parity for comm/compute overlap.

    v_l: (n_local, m) local row block. Returns (w_l, rel_error) with
    rel_error the *global* ||V - WH||_F / ||V||_F.
    """
    n_l, m = v_l.shape
    axis_size = n_total // n_l  # shapes are static under shard_map/vmap
    idx = jax.lax.axis_index(axis)
    active = jnp.arange(k_pad) < k_eff
    kw, kh = jax.random.split(key)
    v_mean = jax.lax.psum(jnp.sum(v_l), axis) / (n_total * m)
    scale = jnp.sqrt(jnp.maximum(v_mean, _EPS) / k_eff)
    # replicated full-shape draw, then slice this shard's rows — bit-compatible
    # with the single-device init (the Gram psums below are where fp order
    # can differ, not the init)
    w_full = scale * jax.random.uniform(kw, (n_total, k_pad), v_l.dtype, 0.1, 1.0)
    w_l = jax.lax.dynamic_slice_in_dim(w_full, idx * n_l, n_l, axis=0)
    h = scale * jax.random.uniform(kh, (k_pad, m), v_l.dtype, 0.1, 1.0)
    w_l = w_l * active[None, :]
    h = h * active[:, None]

    w_l, h = _mu_sweeps(v_l, w_l, h, active, iters, axis, comm, axis_size)
    sq = jax.lax.psum(jnp.sum((v_l - w_l @ h) ** 2), axis)
    vsq = jax.lax.psum(jnp.sum(v_l**2), axis)
    err = jnp.sqrt(sq) / jnp.maximum(jnp.sqrt(vsq), _EPS)
    return w_l, err


def _dnmf_masked_chunk_local(
    v_l: Array,
    w_l: Array,
    h: Array,
    k_eff: Array,
    k_pad: int,
    chunk: int,
    axis: str,
    axis_size: int,
    comm: str = "sync",
    steps: Array | None = None,
) -> tuple[Array, Array, Array]:
    """Resumable chunk of a masked data-sharded fit: ``chunk`` MU sweeps
    (per-lane gated to ``steps`` when given) plus the *global* rel_error
    from the existing psum structure.

    The elastic executor's convergence gate under data sharding: the
    residual ``||V - WH||_F / ||V||_F`` is assembled from per-shard squared
    sums with the same two psums the Gram updates already pay, so testing
    convergence at a chunk boundary costs one extra scalar all-reduce pair
    — no gather of V or W. ``comm="pipelined"`` runs the one-sweep-stale
    overlapped schedule *within* the chunk (each chunk closes with one
    synchronous sweep, exactly like a short ``_mu_sweeps`` run).

    v_l: (n_local, m) row block; w_l: (n_local, k_pad) local rows; h
    replicated. Returns (w_l, h, rel_error) with rel_error replicated.
    """
    active = jnp.arange(k_pad) < k_eff
    w_l, h = _mu_sweeps(v_l, w_l, h, active, chunk, axis, comm, axis_size, steps=steps)
    sq = jax.lax.psum(jnp.sum((v_l - w_l @ h) ** 2), axis)
    vsq = jax.lax.psum(jnp.sum(v_l**2), axis)
    err = jnp.sqrt(sq) / jnp.maximum(jnp.sqrt(vsq), _EPS)
    return w_l, h, err


def auto_mesh(mesh: Mesh) -> Mesh:
    """``mesh`` with every axis ``AxisType.Auto``.

    ``jax.make_mesh`` defaults to explicit axes, whose arrays carry their
    sharding in the type, so eager host-side indexing of a plane's outputs
    (``scores[:n_real]``, slot writes ``.at[i].set``) is refused. The
    shard_map'd fits here are written for automatic sharding propagation.
    """
    if all(t == AxisType.Auto for t in mesh.axis_types):
        return mesh
    return Mesh(mesh.devices, mesh.axis_names, axis_types=(AxisType.Auto,) * mesh.devices.ndim)


def make_local_mesh(n_devices: int | None = None, axis: str = "data") -> Mesh:
    """1-D mesh over available devices (tests run this with 1 CPU device)."""
    devs = jax.devices()
    n = n_devices or len(devs)
    return jax.make_mesh((n,), (axis,), (AxisType.Auto,), devices=devs[:n])
