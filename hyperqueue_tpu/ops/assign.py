"""Dense scheduling-tick assignment kernel (JAX).

This is the TPU re-host of the reference's per-tick MILP
(crates/tako/src/internal/scheduler/solver.rs:16-461). The reference builds an
integer program with one variable per (worker, rq-batch, variant) and solves it
with HiGHS on the CPU; here the same decision — "how many tasks of each request
class go to each worker this tick" — is computed by a single jit-compiled
program: a `lax.scan` over priority-ordered batches whose body does only dense
(W,) / (W,R) integer vector ops (with gang rows, loops that run each row's
own kind of work), so the whole tick runs on-device with no host
round-trips and fixed (bucketed) shapes.

Semantics preserved from the reference solver:
  * Strict priority dominance with gap relaxation (solver.rs:240-410): batches
    are scanned highest-priority first; a lower batch sees only the free
    resources left after every higher batch packed maximally, which is exactly
    the reference's blocking-constraint-with-gap outcome for a single tick.
  * Resource variants (request.rs:230): each batch carries up to V variant
    need-vectors tried in user preference order.
  * min_time (request.rs:137): a variant is masked off on workers whose
    remaining lifetime is shorter.
  * Worker objective weights (solver.rs:520-549): the water-fill visits
    workers in an order that penalizes burning scarce resources a batch does
    not request, then lower index first.

Inputs are all integers (fixed-point resource fractions); no floating-point
feasibility drift is possible.

Shapes (padded to buckets by the caller, models/greedy.py):
  free      (W, R) int32   free resource fractions per worker
  nt_free   (W,)   int32   remaining simultaneous-task slots per worker
  lifetime  (W,)   int32   remaining worker lifetime seconds (INF_TIME if none)
  needs     (B, V, R) int32  per-batch per-variant request vector; an all-zero
                             variant row is "variant absent"
  sizes     (B,)   int32   number of ready tasks in the batch (0 = padding row)
  min_time  (B, V) int32   per-variant minimal task duration in seconds
  scarcity  (R,)   float32 precomputed scarcity weight per resource
Output:
  counts    (B, V, W) int32  tasks of batch b, variant v to start on worker w
"""

from __future__ import annotations

import functools

# INF_TIME is re-exported here for kernel callers/tests
from hyperqueue_tpu.utils.constants import INF_TIME  # noqa: F401

# jax is imported LAZILY: the host-side functions in this module
# (host_visit_classes, scarcity_weights, greedy_cut_scan_numpy) are pure
# numpy and serve the CPU production path, where pulling in jax costs
# several seconds of server/worker startup per process (measured ~4 s
# cold).  _load_jax() installs jax/jnp into the module globals the first
# time a kernel entry point actually runs.
jax = None
jnp = None


def _load_jax() -> None:
    global jax, jnp
    if jax is None:
        import jax as _jax
        import jax.numpy as _jnp

        jax = _jax
        jnp = _jnp
# Quantization of the waste score into the integer sort key: key =
# waste_q * W + worker_index, waste_q in [0, _WASTE_Q]. With W <= 16384 the
# key stays well inside int32.
_WASTE_Q = 65536

# Policy affinity weights are clamped to [0, _AFF_MAX] before quantization;
# the visit-class key combines (-affinity, waste) lexicographically, so the
# affinity term needs a multiplier strictly above the waste range.
_AFF_MAX = 256.0
_AFF_STRIDE = _WASTE_Q * 2  # > max waste_q (waste <= 1 since scarcity sums to 1)


MAX_KERNEL_AMOUNT = 2**23  # all amounts must be below this (float32-exact)


def _variant_capacity(free, nt_free, need, time_ok, total=None, all_r=None):
    """(W,) int32: how many tasks of `need` fit on each worker right now.

    TPUs have no hardware integer division; XLA expands `//` into a long
    scalar sequence that dominated the scan. Instead: float32 division plus an
    exact integer fixup. Precondition (enforced by the range compression in
    scheduler/tick.py / models/greedy.py): free and need < 2^23, so both are
    exactly representable in float32 and the float quotient is within 1 of
    the true floor — two int32 multiply-compare corrections make it exact.

    all_r (R,) int32 0/1 marks ALL-policy resources (request.rs:14-21 All):
    the task takes the worker's ENTIRE pool of that resource, so it fits only
    where the pool is untouched (free == total, reference solver.rs:120-124
    amount_or_none_if_all) — at most one such task per worker per tick.
    """
    needed = need > 0
    denom = jnp.where(needed, need, 1)
    q = jnp.floor(
        free.astype(jnp.float32) * (1.0 / denom.astype(jnp.float32))[None, :]
    ).astype(jnp.int32)
    # exact floor-division fixup (all int32 multiplies)
    too_big = q * denom[None, :] > free
    q = q - too_big.astype(jnp.int32)
    too_small = (q + 1) * denom[None, :] <= free
    q = q + too_small.astype(jnp.int32)
    per_res = jnp.where(needed[None, :], q, jnp.int32(2**30))
    any_req = jnp.any(needed)
    if all_r is not None:
        is_all = all_r > 0
        all_fit = ((free == total) & (total > 0)).astype(jnp.int32)
        per_res = jnp.where(
            is_all[None, :], all_fit, per_res
        )
        any_req = any_req | jnp.any(is_all)
    cap = jnp.min(per_res, axis=1)
    cap = jnp.minimum(cap, nt_free)
    cap = jnp.where(time_ok, cap, 0)
    # an absent (all-zero) variant must contribute nothing
    cap = jnp.where(any_req, cap, 0)
    return jnp.maximum(cap, 0)


def _exclusive_prefix_rows(x):
    """Exclusive prefix sum along axis 0 of an int32 array ((W,) or (W, C)),
    mod 2**32: bit for bit `np.cumsum(x, 0) - x` in int32, wrap-around
    included, for every input.

    Written as log-step shifted adds (Hillis-Steele): ceil(log2 W) times,
    `y += y shifted down by d rows, zeros shifted in`, d = 1, 2, 4, ...;
    after the last, row w holds the sum of rows 0..w, and taking `x` off
    makes it exclusive. Nothing but int32 adds, so exactness needs no
    argument and holds on every backend, and the only thing the trace
    adapts to is the static row count (a (1,) input takes no step at all).

    Why not `jnp.cumsum`: on a TPU it lowers to a `reduce-window`, which
    costs 12.5 us a call inside the scan at 1 024 rows and the same at
    4 096 — a fixed cost of that lowering, 86% of the whole kernel (PERF.md
    section 6, PR 28). Ten or twelve fused shifted adds cost 0.9 / 1.6 us.
    A two-level blocked triangular contraction on the MXU (8-bit limbs in
    bfloat16 or 7-bit limbs in int8, exact as well) was measured beside it
    and is 1 us a call slower at both widths: its flops are free, its limb
    split, relayout and recombination are not (the table of every
    formulation timed is in PERF.md section 6).
    """
    _load_jax()
    n = x.shape[0]
    y, d = x, 1
    while d < n:
        # rows d.. take rows 0..n-d; the negative high edge drops the rest
        shift = [(d, -d, 0)] + [(0, 0, 0)] * (x.ndim - 1)
        y = y + jax.lax.pad(y, jnp.int32(0), shift)
        d *= 2
    return y - x


# the one way the jitted kernel takes a prefix, as `hq_solve_prefix_total`
# labels it (scheduler/tick.py); the host twins keep `np.cumsum`
PREFIX_FORMULATION = "shifted-adds"


def _water_fill_classed(
    cap, remaining, class_onehot, per_class_total=None, same_class_before=0
):
    """Water-fill in (waste-class asc, worker-index asc) visit order without
    any sort or permutation gather.

    class_onehot: (W, C) int32 0/1, class 0 visited first; within a class,
    workers are visited in index order. The prefix (capacity absorbed before
    worker w) = total capacity of strictly-lower classes + exclusive
    index-order prefix sum within w's own class — all elementwise ops,
    column sums and two exclusive prefixes, where a 1024-element
    permutation gather costs ~140us. Both prefixes (over the 16 classes and
    over the W workers of each class column) are `_exclusive_prefix_rows`:
    log-step shifted adds in int32, exact, about a microsecond a call on a
    TPU; as a `cumsum` (a `reduce-window` there) the one over the workers
    was 12.5 us a call and most of the kernel.

    The multi-chip kernel runs this SAME function on each worker shard
    (parallel/solve.py): `per_class_total` (C,) is then the cluster-wide
    per-class capacity (local sums by default — the single-chip case) and
    `same_class_before` (C,) the same-class capacity on lower-index devices
    (0 single-chip), which together shift each local prefix to its global
    position. Returns (assign (W,), assigned_total = min(remaining, total
    capacity) — the global total even when workers are sharded).
    """
    _load_jax()
    cap_c = cap[:, None] * class_onehot  # (W, C)
    per_class = jnp.sum(cap_c, axis=0)  # (C,)
    if per_class_total is None:
        per_class_total = per_class
    class_before = _exclusive_prefix_rows(per_class_total)  # (C,)
    within_excl = _exclusive_prefix_rows(cap_c)  # (W, C)
    prefix = jnp.sum(
        (within_excl + (class_before + same_class_before)[None, :])
        * class_onehot,
        axis=1,
    )
    assign = jnp.clip(remaining - prefix, 0, cap)
    # water-fill identity: total assigned = min(remaining, total capacity)
    # (cap >= 0 everywhere, prefix is the exact global exclusive prefix) —
    # no reduction over `assign` needed, which on a sharded axis would cost
    # a second collective
    return assign, jnp.minimum(remaining, jnp.sum(per_class_total))


# fixed class-axis width for the gather-free water-fill; distinct waste
# levels per mask are bounded by distinct worker resource patterns and are
# clamped here (overflowing classes merge into the last one, which only
# relaxes the preference order among the most-wasteful workers)
N_VISIT_CLASSES = 16


def host_visit_classes(free0, needs, scarcity, all_mask=None, affinity=None):
    """Precompute worker visit classes per distinct request mask (numpy).

    The preference order (avoid burning scarce resources a request does not
    need, then lower worker index — reference solver.rs:520-549 objective) is
    a per-tick static choice depending only on (a) which resources each
    request does NOT use and (b) which resources each worker has. Distinct
    "unused resource" masks per tick are few (M << B*V). Instead of materializing
    permutations (arbitrary-permutation gathers cost ~140us per scan step on
    TPU), each worker gets a visit CLASS = dense rank of its waste score; the
    kernel water-fills class-by-class with column sums and prefix sums only
    (`_exclusive_prefix_rows`: shifted int32 adds, about a microsecond each).

    affinity (B, W) float, optional: per-(batch, worker) policy weight (the
    heterogeneity matrix `S` sliced per batch row). The visit key becomes the
    lexicographic pair (-affinity, waste): higher-throughput workers are
    water-filled first, waste breaks ties. Deduplication then keys on (mask,
    affinity row) so two batches with identical request shapes but different
    weight rows get distinct classes. With affinity=None the behavior is
    bit-identical to the unweighted kernel.

    Returns (class_m (M, W) int32 in [0, N_VISIT_CLASSES), order_ids (B, V)
    int32). Only ~M*W ints cross the host->device boundary per tick.
    """
    import numpy as np

    n_b, n_v, _n_r = needs.shape
    has = np.asarray(free0) > 0  # (W, R)
    masks = np.asarray(needs) == 0  # (B, V, R): resources NOT requested
    if all_mask is not None:
        # an ALL-policy entry requests the resource (amount is the pool)
        masks = masks & ~(np.asarray(all_mask) > 0)
    flat = masks.reshape(n_b * n_v, -1)
    if affinity is None:
        uniq, inverse = np.unique(flat, axis=0, return_inverse=True)
        aff_u = None
    else:
        aff = np.clip(np.asarray(affinity, dtype=np.float64), 0.0, _AFF_MAX)
        aff_q = np.round(aff * _WASTE_Q).astype(np.int64)  # (B, W)
        aff_rep = np.repeat(aff_q, n_v, axis=0)  # (B*V, W)
        combined = np.concatenate([flat.astype(np.int64), aff_rep], axis=1)
        _u, index, inverse = np.unique(
            combined, axis=0, return_index=True, return_inverse=True
        )
        uniq = flat[index]
        aff_u = aff_rep[index]  # (M, W)
    weighted = has * np.asarray(scarcity)[None, :]  # (W, R)
    waste = np.einsum("mr,wr->mw", uniq.astype(np.float32), weighted)
    waste_q = np.round(waste * _WASTE_Q).astype(np.int64)
    key = waste_q if aff_u is None else waste_q - aff_u * np.int64(_AFF_STRIDE)
    class_m = np.empty_like(key, dtype=np.int32)
    for m in range(key.shape[0]):
        levels = np.unique(key[m])  # sorted ascending
        class_m[m] = np.searchsorted(levels, key[m]).astype(np.int32)
    np.clip(class_m, 0, N_VISIT_CLASSES - 1, out=class_m)
    order_ids = inverse.reshape(n_b, n_v).astype(np.int32)
    return class_m, order_ids


def expand_onehots(class_m, order_ids):
    """Per-batch visit-class one-hots (B, V, W, C) int32 — built with one
    broadcasted compare outside the scan. The optimization barrier stops
    XLA from fusing this into the scan body (it would re-gather
    class_m[order_ids[i]] every step — a dynamic row gather costing
    ~140us/step; measured 84ms vs 0.1ms for the whole tick)."""
    _load_jax()
    class_ids = class_m[order_ids]  # (B, V, W)
    onehots = (
        class_ids[..., None]
        == jnp.arange(N_VISIT_CLASSES, dtype=jnp.int32)
    ).astype(jnp.int32)
    return jax.lax.optimization_barrier(onehots)


# the scope of the one-chip gang selection in a trace's op metadata
GANG_SELECT_SCOPE = "hq_gang_select"


def _group_counts(elig, group_onehot, mine=None):
    """Eligible workers per group, (G,); with `mine` (W,) 0/1, (2, G): the
    row's own reserved ones, then all.  Two (W, G) reductions: one over a
    (W, 2, G) stack made a solve of 512 steps 3 ms slower on a v5e."""
    per_group = jnp.sum(elig[:, None] * group_onehot, axis=0)
    if mine is None:
        return per_group
    own = jnp.sum((elig * mine)[:, None] * group_onehot, axis=0)
    return jnp.stack([own, per_group])


def _gang_select_local(
    elig, group_onehot, n, per_group_total=None, same_group_before=0,
    mine=None,
):
    """Pick the gang's worker set from one device's full worker view.

    elig (W,) int32 0/1, group_onehot (W, G) int32, n scalar gang size.
    Chooses the FIRST group with >= n eligible workers (else the group with
    the most, for holdback), then the n lowest-index eligible members.
    Returns (take (W,) int32 0/1, any_feasible bool).

    `mine` (W,) int32 0/1, optional (`gang_resv`): the row's own reserved
    workers, a subset of `elig`. The first group with >= n eligible ones
    among them is chosen ahead of the rest, and the n lowest-index of
    them taken; with no such group the selection is as above.  Both
    counts come from `_group_counts`, and one prefix runs.

    Single-chip callers leave the two count arguments at their defaults.
    The sharded kernel passes the cross-device terms (parallel/solve.py):
    `per_group_total` (G,) is then the cluster-wide eligible count per
    group (elig/group_onehot cover only this device's workers) and
    `same_group_before` (G,) the same-group eligible count on lower-index
    devices, so "the n lowest-index members" counts across the mesh; with
    `mine` both are (2, G), own then all, as `_group_counts` gives them.
    """
    if per_group_total is None:
        per_group_total = _group_counts(elig, group_onehot, mine)
    if mine is None:
        counts = per_group_total
    else:
        own_feas = per_group_total[0] >= n
        own = jnp.any(own_feas)
        counts = per_group_total[1]
    feasible = counts >= n
    any_feas = jnp.any(feasible)
    chosen = jnp.where(any_feas, jnp.argmax(feasible), jnp.argmax(counts))
    if mine is not None:
        chosen = jnp.where(own, jnp.argmax(own_feas), chosen)
        elig = jnp.where(own, elig * mine, elig)
        if jnp.ndim(same_group_before):
            same_group_before = jnp.where(
                own, same_group_before[0], same_group_before[1])
    chosen_oh = (
        jnp.arange(group_onehot.shape[1], dtype=jnp.int32) == chosen
    ).astype(jnp.int32)
    sel = elig * jnp.sum(group_onehot * chosen_oh[None, :], axis=1)
    prefix = _exclusive_prefix_rows(sel) + jnp.sum(
        same_group_before * chosen_oh
    )
    take = sel * (prefix < n).astype(jnp.int32)
    return take, any_feas


# a reservation code that names no gang row of the solve: the worker is
# reserved for a gang the solve does not carry (`gang_resv`)
RESV_ELSEWHERE = 1 << 30


def scan_batches(
    free, nt_free, lifetime, needs, sizes, min_time, onehots, water_fill,
    total=None, all_mask=None,
    gang_nodes=None, gang_ok=None, group_onehot=None, gang_select=None,
    policy_mask=None, gang_resv=None,
):
    """Scan priority-ordered batches, water-filling each over the workers.

    The ONE scan body shared by the single-chip and multi-chip kernels —
    parity between them is structural, not test-maintained: the sharded path
    (parallel/solve.py) differs only in the `water_fill` it plugs in (its
    prefix spans devices via all_gather).

    water_fill(cap, remaining, class_onehot) -> (assign (W,), assigned_total);
    `assigned_total` must be the GLOBAL total when workers are sharded.
    total (W, R) and all_mask (B, V, R) enable ALL-policy requests: an
    assigned ALL task drains the worker's whole pool of the marked resources
    (reference solver.rs:120-124). Returns (counts, free_after,
    nt_free_after).

    Gang rows (all-or-nothing column groups): gang_nodes (B,) int32 marks
    batch rows that are one multi-node gang each (0 = ordinary row);
    gang_ok (W,) int32 0/1 is host idleness (a gang member must be fully
    idle — prefilled backlog does not show in `free`, so free==total is NOT
    sufficient); group_onehot (W, G) int32 maps workers to worker groups.
    The scan carries a gang-availability vector that starts at gang_ok and
    is zeroed by ANY in-scan assignment, so a gang only sees workers still
    untouched this solve. A feasible gang row emits n co-scheduled counts
    in variant 0; feasible or not, the selected workers are HELD (free/nt
    zeroed) for the rest of the scan — the in-solve equivalent of the host
    `mn_reserved` reservation drain, so lower-priority work cannot steal
    members while a gang accumulates.  A solve with gang rows runs each
    row's own work alone (`scan_step_kinds` counts it): the selection on
    a gang row and no water-fill, the water-fill on any other row and no
    selection, and nothing past the last live row; a solve without them
    is the one `lax.scan` of water-fills over every row.

    policy_mask (B, W) int32 0/1, optional: zero marks workers a batch's
    policy weight row excludes (affinity 0 = hard incompatibility per the
    Gavel throughput-matrix semantics). A masked worker contributes no
    capacity to the batch and is ineligible as a gang member. Callers pass
    it only when at least one zero exists; the all-ones mask is the None
    path.

    gang_resv (W,) int32, optional (`--gang-drain busy`; with gang rows
    only): the reservation of each worker, b + 1 where it is reserved for
    the gang of row b, RESV_ELSEWHERE for a gang the solve does not carry,
    0 for none.  A reserved worker offers no capacity to any single-node
    row, and no gang row but its own sees it.  A gang row whose own
    reserved workers count n eligible takes n of them (the first group in
    group order that has n, the lowest-index members there), ahead of
    every other group; else it selects as above among the workers that
    are reserved for no other gang.  None is the path without
    reservations, unchanged.
    """
    _load_jax()
    n_variants = needs.shape[1]
    has_all = all_mask is not None
    has_gang = gang_nodes is not None
    has_resv = has_gang and gang_resv is not None
    has_pmask = policy_mask is not None
    unreserved = None
    if has_resv:
        unreserved = (gang_resv == 0).astype(jnp.int32)
    if has_gang and gang_select is None:
        # the one-chip selection, named in a trace's op metadata as the
        # sharded one's gather is (parallel/solve.py GANG_SELECT_GATHER)
        def gang_select(elig, group_onehot, n, mine=None):
            with jax.named_scope(GANG_SELECT_SCOPE):
                return _gang_select_local(elig, group_onehot, n, mine=mine)

    def fill(free, nt_free, gang_avail, remaining, b_needs, b_min_time,
             b_onehot, b_all, b_pmask):
        """A single-node row: water-fill its size over the variants."""
        counts_v = []
        for v in range(n_variants):  # V is tiny and static: unrolled
            need = b_needs[v]
            time_ok = b_min_time[v] <= lifetime
            all_r = b_all[v] if has_all else None
            cap = _variant_capacity(
                free, nt_free, need, time_ok, total=total, all_r=all_r
            )
            cap = jnp.minimum(cap, remaining)
            if has_pmask:
                cap = cap * b_pmask
            if has_resv:
                cap = cap * unreserved
            assign, assigned = water_fill(cap, remaining, b_onehot[v])
            remaining = remaining - assigned
            free = free - assign[:, None] * need[None, :]
            if has_all:
                # an ALL assignment (assign is 0/1 there: cap <= 1) empties
                # the worker's pool of the marked resources
                free = free * (1 - assign[:, None] * all_r[None, :])
            nt_free = nt_free - assign
            if has_gang:
                gang_avail = gang_avail * (assign == 0).astype(jnp.int32)
            counts_v.append(assign)
        return free, nt_free, gang_avail, jnp.stack(counts_v)

    if not has_gang:
        def batch_body(carry, batch):
            batch = list(batch)
            b_needs, b_size, b_min_time, b_onehot = batch[:4]
            rest = batch[4:]
            b_all = rest.pop(0) if has_all else None
            b_pmask = rest.pop(0) if has_pmask else None
            free, nt_free, _, counts = fill(
                *carry, None, b_size, b_needs, b_min_time, b_onehot, b_all,
                b_pmask,
            )
            return (free, nt_free), counts

        xs = (needs, sizes, min_time, onehots)
        if has_all:
            xs = xs + (all_mask,)
        if has_pmask:
            xs = xs + (policy_mask,)
        (free, nt_free), counts = jax.lax.scan(
            batch_body, (free, nt_free), xs
        )
        return counts, free, nt_free

    # With gang rows each row does its own kind's work alone: a gang row's
    # water-fill would spend a size of 0 and a single-node row's selection
    # would be masked off, so skipping either leaves every result bit for
    # bit as it is, and the rows past the last live one do nothing.  No
    # conditional decides it (one costs 3.1 us a step on a v5e, PERF.md
    # section 6): a loop over the gang rows in order runs the single-node
    # rows before each in a loop of their own, then the gang row; a last
    # loop runs the single-node rows after the last gang row.
    def row(x, i):
        return None if x is None else jax.lax.dynamic_index_in_dim(
            x, i, keepdims=False)

    def put_row(counts, i, counts_i):
        return jax.lax.dynamic_update_index_in_dim(counts, counts_i, i, 0)

    def fill_row(state):
        i, (free, nt_free, gang_avail), counts = state
        free, nt_free, gang_avail, counts_i = fill(
            free, nt_free, gang_avail, row(sizes, i), row(needs, i),
            row(min_time, i), row(onehots, i), row(all_mask, i),
            row(policy_mask, i),
        )
        return i + 1, (free, nt_free, gang_avail), put_row(counts, i, counts_i)

    def fill_rows(i, stop, carry, counts):
        return jax.lax.while_loop(
            lambda state: state[0] < stop, fill_row, (i, carry, counts))

    def gang_row(i, carry, counts):
        """A gang row: select its members, emit them if the gang is
        feasible, and HOLD them either way."""
        free, nt_free, gang_avail = carry
        time_ok0 = (row(min_time, i)[0] <= lifetime).astype(jnp.int32)
        elig = (
            gang_avail * time_ok0
            * (nt_free >= 1).astype(jnp.int32)
        )
        if has_pmask:
            elig = elig * row(policy_mask, i)
        mine = None
        if has_resv:
            # the code of row b is b + 1 (gang_resv above)
            mine = (gang_resv == i + 1).astype(jnp.int32)
            elig = elig * jnp.maximum(unreserved, mine)
        take, any_feas = gang_select(
            elig, group_onehot, row(gang_nodes, i), mine)
        emit = take * any_feas.astype(jnp.int32)
        free = free * (1 - take)[:, None]
        nt_free = nt_free * (1 - take)
        gang_avail = gang_avail * (1 - take)
        counts_i = jnp.zeros((n_variants,) + emit.shape, jnp.int32)
        return (free, nt_free, gang_avail), put_row(
            counts, i, counts_i.at[0].set(emit))

    n_b = needs.shape[0]
    rows = jnp.arange(n_b, dtype=jnp.int32)
    is_gang = gang_nodes > 0
    n_live = jnp.max(jnp.where(is_gang | (sizes > 0), rows + 1, 0))

    def next_gang_row(i):
        return jnp.min(jnp.where(is_gang & (rows >= i), rows, n_b))

    def to_gang_row(state):
        i, g, carry, counts = state
        i, carry, counts = fill_rows(i, g, carry, counts)
        carry, counts = gang_row(g, carry, counts)
        return g + 1, next_gang_row(g + 1), carry, counts

    counts = jnp.zeros((n_b, n_variants, free.shape[0]), jnp.int32)
    carry = (free, nt_free, gang_ok.astype(jnp.int32))
    i, _, carry, counts = jax.lax.while_loop(
        lambda state: state[1] < n_b, to_gang_row,
        (jnp.int32(0), next_gang_row(0), carry, counts))
    _, (free, nt_free, _), counts = fill_rows(i, n_live, carry, counts)
    return counts, free, nt_free


def scan_step_kinds(gang_nodes, sizes) -> dict:
    """What `scan_batches` does with each row of a solve that carries gang
    rows (numpy, host; the padded (B,) inputs): a `gang` row selects and
    holds its members, a `fill` row (any other row up to the last live
    one) water-fills its size, an `idle` row (past the last live one) is
    never visited."""
    import numpy as np

    is_gang = np.asarray(gang_nodes) > 0
    live = np.flatnonzero(is_gang | (np.asarray(sizes) > 0))
    n_live = int(live[-1]) + 1 if live.size else 0
    n_gang = int(is_gang.sum())
    return {"gang": n_gang, "fill": n_live - n_gang,
            "idle": len(is_gang) - n_live}


def greedy_cut_scan_impl(
    free, nt_free, lifetime, needs, sizes, min_time, class_m, order_ids,
    total=None, all_mask=None,
    gang_nodes=None, gang_ok=None, group_onehot=None, policy_mask=None,
    gang_resv=None,
):
    """Single-chip kernel: one-hot expansion + the shared batch scan.

    Un-jitted implementation (jit-wrapped below; also reused by the driver
    entry). class_m (M, W) int32 + order_ids (B, V) int32 come from
    host_visit_classes: per distinct request mask, each worker's visit class
    (0 = visited first). total/all_mask enable ALL-policy requests;
    gang_nodes/gang_ok/group_onehot enable all-or-nothing gang rows (see
    scan_batches). See module docstring for shapes/semantics. Returns
    (counts, free_after, nt_free_after).
    """
    onehots = expand_onehots(class_m, order_ids)
    return scan_batches(
        free, nt_free, lifetime, needs, sizes, min_time, onehots,
        _water_fill_classed, total=total, all_mask=all_mask,
        gang_nodes=gang_nodes, gang_ok=gang_ok, group_onehot=group_onehot,
        policy_mask=policy_mask, gang_resv=gang_resv,
    )


_greedy_cut_scan_jit = None


def greedy_cut_scan(*args, **kwargs):
    """Jitted single-chip kernel (donate_argnums=(0, 1): the free/nt_free
    device buffers are consumed and their storage reused for the outputs).
    The jit wrapper is built on first call so importing this module never
    pulls in jax (see _load_jax)."""
    global _greedy_cut_scan_jit
    if _greedy_cut_scan_jit is None:
        _load_jax()
        _greedy_cut_scan_jit = functools.partial(
            jax.jit, donate_argnums=(0, 1)
        )(greedy_cut_scan_impl)
    return _greedy_cut_scan_jit(*args, **kwargs)


def greedy_cut_scan_numpy(
    free, nt_free, lifetime, needs, sizes, min_time, class_m, order_ids,
    total=None, all_mask=None,
    gang_nodes=None, gang_ok=None, group_onehot=None, policy_mask=None,
    gang_resv=None,
):
    """Vectorized numpy implementation of the cut-scan (identical semantics).

    The jitted scan is the TPU path; on CPU the XLA while-loop overhead
    (~70 ms for 512 steps at W=1024) loses to plain numpy (~15 ms), so this
    is the host fallback the model picks when no accelerator is present.
    """
    import numpy as np

    free = np.asarray(free, dtype=np.int64).copy()
    nt_free = np.asarray(nt_free, dtype=np.int64).copy()
    lifetime = np.asarray(lifetime)
    if total is not None:
        total = np.asarray(total, dtype=np.int64)
    n_b, n_v, _n_r = needs.shape
    n_w = free.shape[0]
    counts = np.zeros((n_b, n_v, n_w), dtype=np.int32)
    class_ids = np.asarray(class_m)[np.asarray(order_ids)]  # (B, V, W)
    idx = np.arange(n_w)
    has_gang = gang_nodes is not None
    if has_gang:
        gang_nodes = np.asarray(gang_nodes)
        gang_avail = np.asarray(gang_ok, dtype=bool).copy()
        group_oh = np.asarray(group_onehot, dtype=bool)  # (W, G)
    pmask = (
        np.asarray(policy_mask) > 0 if policy_mask is not None else None
    )  # (B, W) bool
    resv = unreserved = None
    if has_gang and gang_resv is not None:
        resv = np.asarray(gang_resv)
        unreserved = resv == 0

    def select(elig, n):
        """(take, feasible): scan_batches' gang selection."""
        per_group = (elig[:, None] & group_oh).sum(axis=0)  # (G,)
        feasible = per_group >= n
        chosen = int(
            np.argmax(feasible) if feasible.any() else np.argmax(per_group)
        )
        sel = elig & group_oh[:, chosen]
        prefix = np.cumsum(sel) - sel
        return sel & (prefix < n), bool(feasible.any())

    for b in range(n_b):
        remaining = int(sizes[b])
        if has_gang and gang_nodes[b] > 0:
            # all-or-nothing gang row (see scan_batches): feasible -> emit
            # n co-scheduled counts in variant 0; either way HOLD the
            # selected workers for the rest of the scan
            n = int(gang_nodes[b])
            elig = (
                gang_avail
                & (min_time[b, 0] <= lifetime)
                & (nt_free >= 1)
            )
            if pmask is not None:
                elig = elig & pmask[b]
            own = False
            if resv is not None:
                mine = resv == b + 1
                elig = elig & (unreserved | mine)
                take, own = select(elig & mine, n)
            if not own:
                take, feasible = select(elig, n)
            if own or feasible:
                counts[b, 0, take] = 1
            free[take] = 0
            nt_free[take] = 0
            gang_avail[take] = False
            continue
        for v in range(n_v):
            if remaining <= 0:
                break
            need = needs[b, v]
            needed = need > 0
            all_r = (
                np.asarray(all_mask[b, v]) > 0 if all_mask is not None
                else np.zeros_like(needed)
            )
            if not needed.any() and not all_r.any():
                continue
            if needed.any():
                per_res = np.min(
                    free[:, needed]
                    // np.asarray(need, dtype=np.int64)[needed],
                    axis=1,
                )
            else:
                per_res = np.full(n_w, 2**30, dtype=np.int64)
            if all_r.any():
                # ALL-policy resources: fits only on a fully idle pool,
                # at most one task per worker (solver.rs:120-124)
                all_fit = (
                    (free[:, all_r] == total[:, all_r])
                    & (total[:, all_r] > 0)
                ).all(axis=1)
                per_res = np.minimum(per_res, all_fit.astype(np.int64))
            cap = np.minimum(per_res, nt_free)
            cap[min_time[b, v] > lifetime] = 0
            np.clip(cap, 0, remaining, out=cap)
            if pmask is not None:
                cap[~pmask[b]] = 0
            if unreserved is not None:
                cap[~unreserved] = 0
            if not cap.any():
                continue
            order = np.lexsort((idx, class_ids[b, v]))
            cap_sorted = cap[order]
            cum = np.cumsum(cap_sorted)
            take_sorted = np.clip(remaining - (cum - cap_sorted), 0, cap_sorted)
            assign = np.empty(n_w, dtype=np.int64)
            assign[order] = take_sorted
            assigned = int(take_sorted.sum())
            remaining -= assigned
            free -= assign[:, None] * need[None, :]
            if all_r.any():
                free[:, all_r] *= 1 - assign[:, None]
            nt_free -= assign
            if has_gang:
                gang_avail[assign > 0] = False
            counts[b, v] = assign
    return counts, free, nt_free


def scarcity_weights(total_amounts) -> "np.ndarray":
    """(R,) float32 scarcity per resource, normalized to sum 1 (numpy, host).

    Rarer cluster-wide => larger weight. Resources with zero total capacity
    get weight 0 (nobody can waste them). total_amounts: (R,) summed capacity
    across workers.
    """
    import numpy as np

    total = np.asarray(total_amounts, dtype=np.float64)
    present = total > 0
    inv = np.where(present, total.max(initial=0.0) / np.maximum(total, 1.0), 0.0)
    norm = inv.sum()
    if norm <= 0:
        return np.zeros_like(total, dtype=np.float32)
    return (inv / norm).astype(np.float32)
