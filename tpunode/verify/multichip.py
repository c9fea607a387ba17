"""Multi-chip batch ECDSA verification: shard_map over a device mesh.

The BCH 32 MB-block stress config (BASELINE.json configs[4], ~150k sigs in
one block) wants more than one chip.  Signature verification has no
cross-item dependencies (SURVEY.md §2.3: data parallelism IS the north-star
axis; ring/Ulysses-style sequence parallelism is deliberately unnecessary
here and documented as such), so the multi-chip design is pure DP:

* a 1-D ``Mesh`` over all chips, axis ``"batch"``;
* a lane's one wire buffer (kernel.py) sharded along its batch dimension,
  the minor-most axis, so host→device transfer is split per chip;
* ``shard_map`` runs the same single-chip program :func:`kernel.verify_core`
  on each shard — zero inter-chip traffic in the hot loop;
* one ``psum`` over ICI reduces the per-shard valid-counts so every chip
  (and the host, reading one scalar) agrees on the batch verdict count —
  the only collective the algorithm needs.

Pod scale (ISSUE 13): :func:`make_hybrid_mesh` generalizes the 1-D local
mesh to a ``(host, chip)`` grid following the t5x
``create_hybrid_device_mesh`` exemplar (SNIPPETS.md [1]) — data-parallel
lane sharding across hosts with the per-host axis kept local, so the
slow DCN hop only ever carries the batch split and the one verdict-count
psum, never table traffic.  ``sharded_verify_fn`` / ``dispatch_raw_sharded``
accept either mesh shape (the batch axis shards over ALL mesh axes
jointly); :func:`host_submesh` slices one host's device row back out as
a 1-D mesh — the fleet dispatcher's per-host device rung
(engine ``mesh_hosts``).  The CPU dryrun path (conftest's 8 virtual host
devices) pins every spec without TPU hardware.

Replaces the capability of the reference's process-parallel verification
(one libsecp256k1 call per tx input across peer threads) at chip scale.
"""

from __future__ import annotations

from typing import Optional, Sequence

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax, shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..trace import span
from .ecdsa_cpu import Point
# Canonical fleet host names: owned by sched.py (next to the AffinityMap
# that seeds rendezvous scores from them, ISSUE 19), re-exported here so
# topology callers keep one import site.
from .sched import host_names
from .kernel import (
    PreparedBatch,
    count_transfer,
    expand_lane,
    prepare_batch,
    prepare_batch_raw,
    verify_core,
)

__all__ = [
    "HYBRID_AXES",
    "make_mesh",
    "make_hybrid_mesh",
    "host_names",
    "host_submesh",
    "sharded_verify_fn",
    "verify_batch_sharded",
    "dispatch_raw_sharded",
]


def make_mesh(n_devices: Optional[int] = None) -> Mesh:
    """A 1-D mesh over the first ``n_devices`` local devices (all, if None)."""
    devs = jax.devices()
    if n_devices is not None:
        devs = devs[:n_devices]
    return Mesh(np.array(devs), ("batch",))


#: Axis names of a hybrid (multi-host) mesh: ``host`` is the slow
#: (DCN/cross-host) axis, ``chip`` the fast per-host (ICI/local) axis.
HYBRID_AXES = ("host", "chip")


def make_hybrid_mesh(
    hosts: Optional[int] = None, chips_per_host: Optional[int] = None
) -> Mesh:
    """A ``(hosts, chips_per_host)`` mesh with the per-host axis kept
    local (the t5x ``create_hybrid_device_mesh`` shape).

    On a real multi-host pod (``jax.process_count() > 1``) the grid comes
    from ``mesh_utils.create_hybrid_device_mesh`` so the ``host`` axis
    follows DCN connectivity and each row holds exactly one process's
    local chips.  In a single process — the CPU dryrun, or a virtual
    topology carved out of one host's chips — local devices are reshaped
    into the requested grid instead (tests pin the 2x4 virtual topology
    on the conftest 8-device CPU mesh).

    Defaults: ``hosts`` = the process count (single-process: one host
    per device), ``chips_per_host`` = the per-host device count.  Raises
    when the requested grid needs more devices than are visible — a pod
    that silently shrank must not masquerade as the requested topology
    (the engine's fleet layer handles shrinking explicitly).
    """
    devs = jax.devices()
    nproc = jax.process_count()
    if nproc > 1:  # pragma: no cover - real pod only (no CI multi-host)
        hosts = nproc if hosts is None else hosts
        if chips_per_host is None:
            chips_per_host = max(1, len(devs) // nproc)
        from jax.experimental import mesh_utils

        grid = mesh_utils.create_hybrid_device_mesh(
            (1, chips_per_host), (hosts, 1), devices=devs
        )
        return Mesh(grid, HYBRID_AXES)
    n = len(devs)
    if hosts is None and chips_per_host is None:
        hosts, chips_per_host = n, 1
    elif hosts is None:
        hosts = max(1, n // chips_per_host)
    elif chips_per_host is None:
        chips_per_host = max(1, n // hosts)
    need = hosts * chips_per_host
    if need > n:
        raise ValueError(
            f"hybrid mesh {hosts}x{chips_per_host} needs {need} devices, "
            f"only {n} visible"
        )
    grid = np.array(devs[:need]).reshape(hosts, chips_per_host)
    return Mesh(grid, HYBRID_AXES)


def host_submesh(
    mesh: Mesh, host_index: int, chips: Optional[int] = None
) -> Mesh:
    """One host's device row of a hybrid mesh as a 1-D local mesh — the
    fleet dispatcher's per-host device rung dispatches whole lanes over
    this (zero cross-host traffic per lane).  ``chips`` keeps only the
    leading that-many devices of the row (the engine's chip-by-chip
    degradation rebuilds here at the largest still-healthy width).  A
    1-D mesh is its own (only) full-width row."""
    if mesh.devices.ndim == 1 and chips is None:
        return mesh
    row = mesh.devices if mesh.devices.ndim == 1 else mesh.devices[host_index]
    devs = list(row.flat)
    if chips is not None:
        devs = devs[:chips]
    return Mesh(np.array(devs), ("batch",))


def _batch_axes(mesh: Mesh):
    """The axis-name spec entry sharding the batch dimension: the single
    name on a 1-D mesh, the name tuple on a hybrid mesh (the batch axis
    shards over host AND chip jointly — pure DP, ISSUE 13)."""
    names = tuple(mesh.axis_names)
    return names if len(names) > 1 else names[0]


_FN_CACHE: dict = {}


def _mesh_is_tpu(mesh: Mesh) -> bool:
    return all(d.platform == "tpu" for d in mesh.devices.flat)


def sharded_verify_fn(
    mesh: Mesh,
    kernel: str = "auto",
    *,
    interpret: bool = False,
    block: Optional[int] = None,
    schnorr_free: bool = False,
):
    """Jitted verify step sharded over ``mesh``: same argument as
    :func:`kernel.verify_device` (a lane's wire buffer, placed by
    :func:`_put_lane`), returns ``(ok: (B,) bool, total: int32)``.

    ``kernel``: "auto" picks the Pallas program per shard on an all-TPU
    mesh (per-shard batch must then be BLOCK-aligned — callers pad), the
    portable XLA program otherwise; "xla" forces the latter (the CPU-mesh
    dryrun path); "pallas" forces the Mosaic program — with
    ``interpret=True`` and a small ``block`` it runs on a CPU mesh, which
    is how tests pin the Pallas-inside-shard_map specs without TPU
    hardware (VERDICT r3 item 7).

    ``schnorr_free`` (ADVICE r5 #3): an ECDSA-only batch may select the
    pallas program variant with the jacobi/parity acceptance pows pruned
    at trace time, exactly like the single-chip dispatcher — callers must
    derive it from ``PreparedBatch.schnorr_free`` (a wrong True would
    accept jacobi/parity forgeries).  The XLA program needs no static
    flag: its runtime lax.cond gating sheds the pows per shard already.

    ``B`` must be a multiple of the mesh size (callers pad; static shapes
    also keep XLA from recompiling across batches).  Cached per mesh and
    program variant so repeated batches reuse the compiled executable.
    """
    if kernel not in ("auto", "pallas", "xla"):
        raise ValueError(f"unknown kernel {kernel!r}: auto|pallas|xla")
    use_pallas = kernel == "pallas" or (
        kernel == "auto" and _mesh_is_tpu(mesh)
    )
    schnorr_free = bool(schnorr_free) and use_pallas
    key = (mesh, use_pallas, interpret, block, schnorr_free)
    cached = _FN_CACHE.get(key)
    if cached is not None:
        return cached
    # On a hybrid mesh the batch dimension shards over host AND chip
    # jointly (axis-name tuple) — same program, wider denominator.
    axes = _batch_axes(mesh)

    if use_pallas:
        from functools import partial

        from .pallas_kernel import verify_blocked_impl

        kw = {}
        if interpret:
            kw["interpret"] = True
        if block is not None:
            kw["block"] = block
        if schnorr_free:
            kw["schnorr_free"] = True
        _core = partial(verify_blocked_impl, **kw)
    else:
        _core = verify_core

    def step(buf):
        ok = _core(*expand_lane(buf))
        total = lax.psum(jnp.sum(ok.astype(jnp.int32)), axes)
        return ok, total

    # check_vma off: verify_core's scan carry starts from a broadcast
    # constant (INFINITY), which the varying-manual-axes analysis rejects
    # even though the program is shard-correct (pure DP + one psum).
    sharded = shard_map(
        step,
        mesh=mesh,
        in_specs=(P(None, axes),),  # batch is the buffer's trailing axis
        out_specs=(P(axes), P()),
        check_vma=False,
    )
    fn = jax.jit(sharded)
    _FN_CACHE[key] = fn
    return fn


def _mesh_quantum(mesh: Mesh) -> int:
    """Per-batch size quantum: Pallas shards need BLOCK-aligned per-shard
    batches; the XLA program just needs a multiple of the mesh size."""
    n = mesh.devices.size
    if _mesh_is_tpu(mesh):
        from .pallas_kernel import BLOCK

        return n * BLOCK
    return n


def _put_lane(prep: PreparedBatch, mesh: Mesh) -> jax.Array:
    """One host-to-device call: the lane's buffer, its batch axis split
    over the mesh."""
    sharding = NamedSharding(mesh, P(None, _batch_axes(mesh)))
    count_transfer(prep.buf)
    return jax.device_put(prep.buf, sharding)


def dispatch_raw_sharded(
    raw, mesh: Mesh, pad_to: Optional[int] = None, kernel: str = "auto"
) -> tuple:
    """ASYNC sharded dispatch of a packed RawBatch (ISSUE 10): host prep
    at a mesh-aligned shape, one ``device_put`` that splits the lane's
    buffer per chip, sharded program enqueue.  Returns the
    ``(ok device array, count)`` handle — collect with
    :func:`kernel.collect_verdicts`; JAX async dispatch means the caller
    can prep the next lane while this one computes, exactly like the
    single-chip :func:`kernel.dispatch_batch_tpu_raw`.

    This is the engine's mesh rung (``VerifyConfig.mesh_devices``): a
    packed full lane shards across chips with zero inter-chip traffic in
    the hot loop.  The CPU-mesh dryrun path (conftest's 8 virtual host
    devices) pins it without TPU hardware.
    """
    from .raw import as_raw_batch

    raw = as_raw_batch(raw)
    quantum = _mesh_quantum(mesh)
    size = max(pad_to or 0, len(raw), 1)
    size = (size + quantum - 1) // quantum * quantum
    with span("verify.prepare", cpu=True):
        prep = prepare_batch_raw(raw, pad_to=size)
    with span("verify.transfer", cpu=True):
        buf = _put_lane(prep, mesh)
    fn = sharded_verify_fn(mesh, kernel, schnorr_free=prep.schnorr_free)
    with span("verify.kernel", cpu=True):
        ok, _total = fn(buf)
    return ok, prep.count


def verify_batch_sharded(
    items: Sequence[tuple[Optional[Point], int, int, int]],
    mesh: Optional[Mesh] = None,
    pad_to: Optional[int] = None,
) -> list[bool]:
    """End-to-end multi-chip verify: host prep, shard over the mesh, run.

    Pads the batch to a multiple of the mesh size (lanes padded with
    ``host_valid=False`` are rejected for free).
    """
    if not items:
        return []
    mesh = mesh or make_mesh()
    quantum = _mesh_quantum(mesh)
    size = pad_to or len(items)
    size = max(size, len(items))
    size = (size + quantum - 1) // quantum * quantum
    prep = prepare_batch(items, pad_to=size)
    # schnorr_free comes from the host prep flags (the ONE safe derivation
    # — kernel.PreparedBatch): an ECDSA-only sharded batch sheds the
    # acceptance pows exactly like the single-chip dispatcher.
    fn = sharded_verify_fn(mesh, schnorr_free=prep.schnorr_free)
    ok, _total = fn(_put_lane(prep, mesh))
    return [bool(b) for b in np.asarray(ok)[: prep.count]]
