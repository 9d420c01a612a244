"""Multi-chip sharded tick solver.

Scaling model: the dense tick state is (W, R) with W = workers — the axis that
grows with cluster size (reference target: 1k workers, BASELINE.json 1M x 1k).
We shard W across a jax.sharding.Mesh axis "w" with shard_map; batches/needs
are replicated (they are tiny: B x V x R ints).

Semantics: IDENTICAL to the single-chip kernel (ops/assign.greedy_cut_scan),
by construction. Both water-fill each (batch, variant) over workers in
(visit-class ascending, global worker index ascending) order, where the visit
classes come from the same host_visit_classes precomputation. shard_map splits
the worker axis contiguously, so "global worker index order" within a class is
exactly (device ascending, local index ascending) — the sharded body computes
each local worker's global water-fill prefix as

    prefix(w) = capacity of strictly-lower classes (cluster-wide)
              + capacity of w's class on lower-index devices
              + exclusive local prefix sum within w's class

All three terms come from ONE all_gather of the per-device (C,)-vector of
per-class capacity sums per variant step (C = N_VISIT_CLASSES = 16) — pure ICI
traffic, no host round-trip, no resharding of the (W, R) state. Exactness is
pinned by tests/test_parallel.py, which asserts bitwise count equality with
the single-chip kernel on random and adversarial instances.

Memory layout note: the per-batch visit-class one-hots (B, V, W, C) are
expanded INSIDE the shard_map body from the worker-sharded class table
(class_m is sharded (M, W/D) per device), so no replicated (B, V, W, C)
tensor is ever materialized — each device builds only its own
(B, V, W/D, C) slice. An earlier revision expanded the one-hots outside the
shard_map, which materialized the full W axis on every device (268 MB at
B=256, W=8192) and dominated the sharded solve's cost.

Reference anchor: the solver IS the production scheduler there too
(crates/tako/src/internal/scheduler/{main.rs:40-46,solver.rs:16-461}); this
module is its multi-device form, selected with `--scheduler=multichip`
(models/multichip.py).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from hyperqueue_tpu.ops.assign import (
    _gang_select_local,
    _group_counts,
    _water_fill_classed,
    expand_onehots,
    scan_batches,
)


# the scopes of the two collectives, as they appear in a trace's op metadata
WATER_FILL_GATHER = "hq_water_fill_gather"
GANG_SELECT_GATHER = "hq_gang_select_gather"


def make_worker_mesh(n_devices: int | None = None) -> Mesh:
    devices = jax.devices()
    if n_devices is not None:
        devices = devices[:n_devices]
    return Mesh(devices, axis_names=("w",))


def _sharded_water_fill_classed(cap, remaining, class_onehot, axis):
    """Classed water-fill with a cluster-wide prefix.

    cap (Wl,), class_onehot (Wl, C): LOCAL worker shards. Returns
    (assign (Wl,), assigned_total int32 replicated). The fill itself IS
    ops.assign._water_fill_classed — this wrapper only gathers the per-class
    capacity sums across devices (the single collective) and feeds them in
    as the global totals + lower-device same-class offsets, so the sharded
    fill reduces to the single-chip one by construction.
    """
    my_dev = jax.lax.axis_index(axis)
    per_class_local = jnp.sum(cap[:, None] * class_onehot, axis=0)  # (C,)
    # named for the profiler: the trace's metadata then says which
    # all-gather belongs to the water-fill (one per scan step)
    with jax.named_scope(WATER_FILL_GATHER):
        all_per_class = jax.lax.all_gather(per_class_local, axis)  # (D, C)
    per_class_global = jnp.sum(all_per_class, axis=0)  # (C,)
    n_dev = all_per_class.shape[0]
    lower_dev = jnp.sum(
        jnp.where(
            (jnp.arange(n_dev) < my_dev)[:, None], all_per_class, 0
        ),
        axis=0,
    )  # (C,) same-class capacity on lower-index devices
    return _water_fill_classed(
        cap, remaining, class_onehot,
        per_class_total=per_class_global,
        same_class_before=lower_dev,
    )


def _sharded_gang_select(elig, group_onehot, n, axis, mine=None):
    """Gang selection with cluster-wide group counts.

    elig (Wl,), group_onehot (Wl, G): LOCAL worker shards. The selection
    itself IS ops.assign._gang_select_local — this wrapper only gathers the
    per-group eligible counts across devices (one (G,)-vector all_gather;
    (2, G) with `mine`, the row's own reserved workers, as
    ops.assign._group_counts stacks them) and feeds them in as the global
    totals + lower-device same-group offsets. shard_map splits the worker
    axis contiguously, so that is the single-chip "first n eligible
    members in global index order".
    """
    my_dev = jax.lax.axis_index(axis)
    per_group_local = _group_counts(elig, group_onehot, mine)  # (G,)|(2, G)
    with jax.named_scope(GANG_SELECT_GATHER):
        all_per_group = jax.lax.all_gather(per_group_local, axis)  # (D, ...)
    n_dev = all_per_group.shape[0]
    lower = (jnp.arange(n_dev) < my_dev)[
        (slice(None),) + (None,) * per_group_local.ndim]
    lower_dev = jnp.sum(
        jnp.where(lower, all_per_group, 0), axis=0,
    )  # same-group eligible workers on lower-index devices
    own = {} if mine is None else {"mine": mine}
    return _gang_select_local(
        elig, group_onehot, n,
        per_group_total=jnp.sum(all_per_group, axis=0),
        same_group_before=lower_dev, **own,
    )


def _sharded_body(
    free, nt_free, lifetime, needs, sizes, min_time, class_m, order_ids,
    total=None, all_mask=None,
    gang_nodes=None, gang_ok=None, group_onehot=None, policy_mask=None,
    gang_resv=None,
):
    """shard_map body: free/nt_free/lifetime/class_m/total are local worker
    shards; needs/sizes/min_time/order_ids/all_mask are replicated. The
    scan itself is ops.assign.scan_batches — the SAME code the single-chip
    kernel runs — with only the water-fill swapped for the
    cluster-wide-prefix variant, so single/multi-chip parity is structural.

    The one-hot expansion happens here, per device, over the LOCAL worker
    slice: class_m arrives as this device's (M, Wl) shard, so the expanded
    (B, V, Wl, C) tensor is 1/D of the full volume (the SAME
    ops.assign.expand_onehots the single-chip kernel uses, barrier
    included).
    """
    onehots = expand_onehots(class_m, order_ids)

    def water_fill(cap, remaining, class_onehot):
        return _sharded_water_fill_classed(cap, remaining, class_onehot, "w")

    def gang_select(elig, goh, n, mine=None):
        return _sharded_gang_select(elig, goh, n, "w", mine)

    return scan_batches(
        free, nt_free, lifetime, needs, sizes, min_time, onehots, water_fill,
        total=total, all_mask=all_mask,
        gang_nodes=gang_nodes, gang_ok=gang_ok, group_onehot=group_onehot,
        gang_select=gang_select if gang_nodes is not None else None,
        policy_mask=policy_mask, gang_resv=gang_resv,
    )


def _sharded_cut_scan_impl(
    mesh: Mesh, free, nt_free, lifetime, needs, sizes, min_time, class_m,
    order_ids, total=None, all_mask=None,
    gang_nodes=None, gang_ok=None, group_onehot=None, policy_mask=None,
    gang_resv=None,
):
    in_specs = [
        P("w", None),              # free
        P("w"),                    # nt_free
        P("w"),                    # lifetime
        P(),                       # needs
        P(),                       # sizes
        P(),                       # min_time
        P(None, "w"),              # class_m (per-mask class table, W-sharded)
        P(),                       # order_ids
    ]
    args = [free, nt_free, lifetime, needs, sizes, min_time, class_m,
            order_ids]
    # optional ALL-policy/gang inputs: None args are dropped from the pytree
    # so the no-ALL/no-gang compiled program is unchanged
    if total is not None:
        in_specs.append(P("w", None))
        args.append(total)
    if all_mask is not None:
        in_specs.append(P())
        args.append(all_mask)
    if gang_nodes is not None:
        in_specs.extend([P(), P("w"), P("w", None)])
        args.extend([gang_nodes, gang_ok, group_onehot])
    if policy_mask is not None:
        in_specs.append(P(None, "w"))  # (B, W) per-batch worker mask
        args.append(policy_mask)
    if gang_resv is not None:
        in_specs.append(P("w"))
        args.append(gang_resv)

    def body(free, nt_free, lifetime, needs, sizes, min_time, class_m,
             order_ids, *extra):
        i = 0
        t = m = gn = go = goh = pm = gr = None
        if total is not None:
            t = extra[i]
            i += 1
        if all_mask is not None:
            m = extra[i]
            i += 1
        if gang_nodes is not None:
            gn, go, goh = extra[i:i + 3]
            i += 3
        if policy_mask is not None:
            pm = extra[i]
            i += 1
        if gang_resv is not None:
            gr = extra[i]
        return _sharded_body(
            free, nt_free, lifetime, needs, sizes, min_time, class_m,
            order_ids, total=t, all_mask=m,
            gang_nodes=gn, gang_ok=go, group_onehot=goh, policy_mask=pm,
            gang_resv=gr,
        )

    return jax.shard_map(
        body,
        mesh=mesh,
        in_specs=tuple(in_specs),
        out_specs=(P(None, None, "w"), P("w", None), P("w")),
        check_vma=False,
    )(*args)


def pack_batch_table(needs, sizes, min_time, order_ids, all_mask=None):
    """The replicated per-batch inputs of one solve as ONE int32 vector
    (host side, numpy).  They change together, whenever the batch order
    does, and a replicated put costs a round trip per device whatever its
    size: packed, a change of order costs the resident tick one put
    instead of five (`sharded_cut_scan_donate` unpacks on the device)."""
    import numpy as np

    parts = [needs, sizes, min_time, order_ids]
    if all_mask is not None:
        parts.append(all_mask)
    return np.concatenate(
        [np.asarray(part, dtype=np.int32).ravel() for part in parts]
    )


def _unpack_batch_table(table, extents, has_all):
    """(needs, sizes, min_time, order_ids, all_mask or None): static
    slices of `pack_batch_table`'s vector, B/V/R from `extents`."""
    n_b, n_v, n_r = extents
    shapes = [(n_b, n_v, n_r), (n_b,), (n_b, n_v), (n_b, n_v)]
    if has_all:
        shapes.append((n_b, n_v, n_r))
    parts, at = [], 0
    for shape in shapes:
        size = math.prod(shape)
        parts.append(table[at:at + size].reshape(shape))
        at += size
    if not has_all:
        parts.append(None)
    return parts


@functools.partial(
    jax.jit, static_argnames=("mesh", "extents", "has_all"),
    donate_argnums=(1, 2),
)
def sharded_cut_scan_donate(
    mesh: Mesh, free, nt_free, lifetime, batch_table, class_m, extents,
    has_all=False, total=None,
    gang_nodes=None, gang_ok=None, group_onehot=None, policy_mask=None,
    gang_resv=None,
):
    """Worker-sharded variant of ops.assign.greedy_cut_scan: identical
    semantics, `free`/`nt_free` DONATED (the input buffers are consumed
    and their storage reused for `free_after`/`nt_after`).

    free/total (W, R), nt_free/lifetime/gang_ok/gang_resv (W,), class_m (M, W),
    policy_mask (B, W) and group_onehot (W, G) sharded on axis "w";
    batch_table/gang_nodes replicated. Returns counts (B, V, W) sharded on
    W, plus free/nt_free after.

    This is the device-resident tick's solve (parallel/resident.py): solve
    N's outputs become solve N+1's inputs without ever crossing the host
    boundary, so the per-tick host->device traffic is only the dirty-row
    delta. Callers MUST not touch the passed free/nt_free arrays again;
    one that wants to keep its inputs passes copies.

    needs/sizes/min_time/order_ids/all_mask arrive as one replicated
    vector (`pack_batch_table`; `extents` = their padded (B, V, R),
    `has_all` says whether all_mask is in it).
    """
    needs, sizes, min_time, order_ids, all_mask = _unpack_batch_table(
        batch_table, extents, has_all
    )
    return _sharded_cut_scan_impl(
        mesh, free, nt_free, lifetime, needs, sizes, min_time, class_m,
        order_ids, total=total, all_mask=all_mask,
        gang_nodes=gang_nodes, gang_ok=gang_ok, group_onehot=group_onehot,
        policy_mask=policy_mask, gang_resv=gang_resv,
    )


@functools.lru_cache(maxsize=4)
def _mesh_shardings(mesh: Mesh):
    """NamedSharding objects per mesh, built once: the production tick
    places tensors every solve, and re-constructing shardings per call is
    avoidable host work on the hot path.

    Returns (w2, w1, rep, cm): (W, R)-sharded, (W,)-sharded, replicated,
    and the (M, W) class-table sharding."""
    return (
        NamedSharding(mesh, P("w", None)),
        NamedSharding(mesh, P("w")),
        NamedSharding(mesh, P()),
        NamedSharding(mesh, P(None, "w")),
    )
