"""Microbenchmark of the water-fill's exclusive prefix over the workers (PR 28).

Times formulations of one operation — the exclusive prefix sum of a
(classes x workers) int32 array along the worker axis, mod 2**32 — inside a
512-step `lax.scan`, the way the cut-scan kernel runs it (ops/assign.py,
`_water_fill_classed`), at 1 024 and 4 096 workers x 16 classes, and checks
each against `np.cumsum` bit for bit.  Two scan bodies: `prefix` (the
operation alone, fed by the carry so nothing hoists) and `fill` (a whole
classed water-fill around it).  Layout `rows` is (W, C), prefix along axis
0; `lanes` is (C, W), prefix along the last axis.

`--kernel` times the whole single-chip kernel (`greedy_cut_scan_impl`, 256
batches x 2 variants) with each formulation in place of
`ops.assign._exclusive_prefix_rows`.

Needs the chip (a time from the CPU backend says nothing): run it through
the chip tool, `python benchmarks/prefix_microbench.py`; `--check` runs the
exactness half alone, anywhere.  One JSON line per (formulation, shape), the
table of PERF.md section 6 (PR 28).
"""

from __future__ import annotations

import argparse
import json
import statistics
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np
from jax import lax

STEPS = 512
CLASSES = 16
MASK30 = (1 << 30) - 1


# --- formulations; each takes (x, axis) with axis 0 (rows) or -1 (lanes) ---

def cumsum(x, axis):
    return jnp.cumsum(x, axis=axis) - x


def assoc_scan(x, axis):
    return lax.associative_scan(jnp.add, x, axis=axis % x.ndim) - x


def hillis_steele(x, axis):
    """Log-step shifted adds; at axis 0 this is the program's
    `_exclusive_prefix_rows`."""
    axis %= x.ndim
    n = x.shape[axis]
    y, d = x, 1
    while d < n:
        pad = [(0, 0, 0)] * x.ndim
        pad[axis] = (d, -d, 0)
        y = y + lax.pad(y, jnp.int32(0), pad)
        d *= 2
    return y - x


def _limbs(x, bits, dtype, axis):
    """x int32 -> its `bits`-wide limbs (low first) side by side on the
    non-prefix axis, as `dtype`; with the count of limbs."""
    n = -(-32 // bits)
    parts = [(x >> (bits * k)) & ((1 << bits) - 1) for k in range(n)]
    return jnp.concatenate(parts, axis=0 if axis else 1).astype(dtype), n


def _recombine(y, n, bits, axis):
    """Inverse of `_limbs` on int32 limb sums: shifts and adds, wrapping."""
    parts = jnp.split(y, n, axis=0 if axis else 1)
    out = parts[0]
    for k in range(1, n):
        out = out + (parts[k] << (bits * k))
    return out


def _triangle_lanes(x, bits, dtype):
    """Exclusive prefix along the last axis of (C, n) by one (n, n) strict
    triangle on the MXU, limbs as extra rows."""
    n = x.shape[-1]
    acc = jnp.int32 if dtype == jnp.int8 else jnp.float32
    tri = (jnp.arange(n)[:, None] < jnp.arange(n)[None, :]).astype(dtype)
    limbs, k = _limbs(x, bits, dtype, -1)
    y = jnp.dot(limbs, tri, preferred_element_type=acc).astype(jnp.int32)
    return _recombine(y, k, bits, -1)


def _triangle_rows(x, bits, dtype):
    n = x.shape[0]
    acc = jnp.int32 if dtype == jnp.int8 else jnp.float32
    tri = (jnp.arange(n)[:, None] > jnp.arange(n)[None, :]).astype(dtype)
    limbs, k = _limbs(x, bits, dtype, 0)
    y = jnp.dot(tri, limbs, preferred_element_type=acc).astype(jnp.int32)
    return _recombine(y, k, bits, 0)


def blocked(t, bits, dtype):
    """Two-level blocked triangular contraction, block `t`."""
    acc = jnp.int32 if dtype == jnp.int8 else jnp.float32

    def lanes(x):
        c, w = x.shape
        nb = w // t
        tri = (jnp.arange(t)[:, None] < jnp.arange(t)[None, :]).astype(dtype)
        limbs, k = _limbs(x, bits, dtype, -1)  # (kC, W)
        y = jnp.dot(limbs.reshape(k * c * nb, t), tri,
                    preferred_element_type=acc)
        within = _recombine(
            y.astype(jnp.int32).reshape(k * c, w), k, bits, -1)
        totals = jnp.sum(x.reshape(c, nb, t), axis=-1)  # (C, nb)
        offs = _triangle_lanes(totals, bits, dtype)
        return (within.reshape(c, nb, t) + offs[:, :, None]).reshape(c, w)

    def rows(x):
        w, c = x.shape
        nb = w // t
        tri = (jnp.arange(t)[:, None] > jnp.arange(t)[None, :]).astype(dtype)
        limbs, k = _limbs(x, bits, dtype, 0)  # (W, kC)
        y = jnp.einsum("ts,bsc->btc", tri, limbs.reshape(nb, t, k * c),
                       preferred_element_type=acc)
        within = _recombine(
            y.astype(jnp.int32).reshape(w, k * c), k, bits, 0)
        totals = jnp.sum(x.reshape(nb, t, c), axis=1)  # (nb, C)
        offs = _triangle_rows(totals, bits, dtype)
        return (within.reshape(nb, t, c) + offs[:, None, :]).reshape(w, c)

    def fn(x, axis):
        return rows(x) if axis == 0 else lanes(x)

    return fn


def blocked_i32_dot(t):
    """The same two levels as int32 dots of the whole values (whatever the
    compiler makes of an s32 dot)."""

    def fn(x, axis):
        if axis == 0:
            x = x.T
        c, w = x.shape
        nb = w // t
        tri = (jnp.arange(t)[:, None] < jnp.arange(t)[None, :]).astype(
            jnp.int32)
        within = jnp.dot(x.reshape(c * nb, t), tri).reshape(c, nb, t)
        totals = jnp.sum(x.reshape(c, nb, t), axis=-1)
        tri2 = (jnp.arange(nb)[:, None] < jnp.arange(nb)[None, :]).astype(
            jnp.int32)
        out = (within + jnp.dot(totals, tri2)[:, :, None]).reshape(c, w)
        return out.T if axis == 0 else out

    return fn


def blocked_vpu(t):
    """Two levels of masked int32 sums on the vector unit (lanes layout)."""

    def fn(x, axis):
        if axis == 0:
            x = x.T
        c, w = x.shape
        nb = w // t
        xb = x.reshape(c, nb, t)
        tri = jnp.arange(t)[:, None] < jnp.arange(t)[None, :]  # [s, t]
        within = jnp.sum(jnp.where(tri, xb[..., :, None], 0), axis=-2)
        totals = jnp.sum(xb, axis=-1)
        tri2 = jnp.arange(nb)[:, None] < jnp.arange(nb)[None, :]
        offs = jnp.sum(jnp.where(tri2, totals[:, :, None], 0), axis=-2)
        out = (within + offs[:, :, None]).reshape(c, w)
        return out.T if axis == 0 else out

    return fn


def blocked_f32_whole(t):
    """NOT exact above 2**24: the whole values through a float32
    contraction (here to show what it costs and that it fails)."""

    def fn(x, axis):
        if axis == 0:
            x = x.T
        c, w = x.shape
        nb = w // t
        tri = (jnp.arange(t)[:, None] < jnp.arange(t)[None, :]).astype(
            jnp.float32)
        within = jnp.dot(
            x.reshape(c * nb, t).astype(jnp.float32), tri,
            precision=lax.Precision.HIGHEST,
        ).astype(jnp.int32).reshape(c, nb, t)
        totals = jnp.sum(x.reshape(c, nb, t), axis=-1)
        offs = jnp.cumsum(totals, axis=-1) - totals
        out = (within + offs[:, :, None]).reshape(c, w)
        return out.T if axis == 0 else out

    return fn


def candidates():
    out = {"cumsum": cumsum, "assoc_scan": assoc_scan,
           "hillis_steele": hillis_steele}
    for t in (128, 256):
        out[f"blocked_bf16_T{t}"] = blocked(t, 8, jnp.bfloat16)
        out[f"blocked_int8_T{t}"] = blocked(t, 7, jnp.int8)
    out["blocked_i32dot_T128"] = blocked_i32_dot(128)
    out["blocked_vpu_T128"] = blocked_vpu(128)
    out["blocked_f32whole_T128"] = blocked_f32_whole(128)
    return out


# --- the two scan bodies ---

def scan_prefix(prefix, axis):
    def run(x0, onehots):
        def body(x, oh):
            p = prefix(x * oh, axis)
            return (x + p + 1) & MASK30, None

        return lax.scan(body, x0, onehots)[0]

    return jax.jit(run)


def scan_null(axis):
    def run(x0, onehots):
        def body(x, oh):
            return (x * oh + x + 1) & MASK30, None

        return lax.scan(body, x0, onehots)[0]

    return jax.jit(run)


def scan_fill(prefix, axis):
    """A classed water-fill a step, as `_water_fill_classed` writes it, in
    either layout."""
    cls = 1 if axis == 0 else 0  # the class axis

    def run(cap0, onehots):
        def body(cap, oh):
            remaining = jnp.int32(100_000)
            cap_c = jnp.expand_dims(cap, cls) * oh
            per_class = jnp.sum(cap_c, axis=axis)  # (C,)
            class_before = jnp.cumsum(per_class) - per_class
            within = prefix(cap_c, axis)
            pre = jnp.sum(
                (within + jnp.expand_dims(class_before, axis % 2)) * oh,
                axis=cls,
            )
            assign = jnp.clip(remaining - pre, 0, cap)
            return (cap + assign + 1) & 0xFFF, None

        return lax.scan(body, cap0, onehots)[0]

    return jax.jit(run)


def timed(fn, *args, repeats=15):
    fn(*args).block_until_ready()  # compile + warm
    laps = []
    for _ in range(repeats):
        t0 = time.perf_counter()
        fn(*args).block_until_ready()
        laps.append(time.perf_counter() - t0)
    return statistics.median(laps) / STEPS * 1e6  # us a step


def exact(prefix, axis, w, rng):
    """Bit for bit against numpy over the value ranges the kernel meets,
    wrap-around included."""
    for hi in (2, 513, 2**23 + 1, 2**30 + 1):
        x = rng.integers(0, hi, size=(w, CLASSES), dtype=np.int64)
        want = (np.cumsum(x, axis=0) - x).astype(np.uint32).astype(np.int64)
        arg = x.astype(np.int32) if axis == 0 else x.T.astype(np.int32)
        got = np.asarray(jax.jit(lambda a: prefix(a, axis))(arg))
        got = (got if axis == 0 else got.T).astype(np.uint32).astype(np.int64)
        if not np.array_equal(got, want):
            return False
    return True


def kernel_times(only):
    """ms a solve of the single-chip kernel at the tick cell's shape key
    (W 1 024) and at one chip's share of the sharded cell's (W 4 096, the
    whole-node inputs on), each formulation patched in over the workers;
    the 16-class prefix and anything narrower than a block stay as the
    program has them."""
    from hyperqueue_tpu.ops import assign

    own = assign._exclusive_prefix_rows
    rng = np.random.default_rng(28)
    n_b, n_v, n_r, n_m = 256, 2, 4, 4
    forms = {"program": None, **candidates()}
    for w, has_all in ((1024, False), (4096, True)):
        free = rng.integers(0, 1_280_000, size=(w, n_r)).astype(np.int32)
        args = (
            rng.integers(0, 64, size=w).astype(np.int32),
            np.full(w, 2**30, np.int32),
            rng.integers(0, 210_000, size=(n_b, n_v, n_r)).astype(np.int32),
            rng.integers(1, 5000, size=n_b).astype(np.int32),
            np.zeros((n_b, n_v), np.int32),
            rng.integers(0, CLASSES, size=(n_m, w)).astype(np.int32),
            rng.integers(0, n_m, size=(n_b, n_v)).astype(np.int32),
        )
        kwargs = {}
        if has_all:
            kwargs = {"total": free.copy(),
                      "all_mask": (rng.random((n_b, n_v, n_r)) < 0.05
                                   ).astype(np.int32)}
        for name, prefix in forms.items():
            if only and name not in only:
                continue

            def patched(x, prefix=prefix):
                if x.ndim == 1 or x.shape[0] % 256:
                    return own(x)
                return prefix(x, 0)

            assign._exclusive_prefix_rows = own if prefix is None else patched
            line = {"kernel": name, "w": w, "whole_node_inputs": has_all}
            try:
                # a fresh function a formulation: jit caches its trace by
                # the function's identity
                fn = jax.jit(
                    lambda *a, **k: assign.greedy_cut_scan_impl(*a, **k))
                put = [jnp.asarray(a) for a in (free,) + args]
                kw = {k: jnp.asarray(v) for k, v in kwargs.items()}
                jax.block_until_ready(fn(*put, **kw))
                laps = []
                for _ in range(15):
                    t0 = time.perf_counter()
                    jax.block_until_ready(fn(*put, **kw))
                    laps.append(time.perf_counter() - t0)
                line["kernel_ms"] = round(statistics.median(laps) * 1e3, 3)
            except Exception as e:  # noqa: BLE001 - a row, not a crash
                line["error"] = f"{type(e).__name__}: {e}"[:300]
            finally:
                assign._exclusive_prefix_rows = own
            print(json.dumps(line), flush=True)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--check", action="store_true",
                    help="exactness only (any backend)")
    ap.add_argument("--kernel", action="store_true",
                    help="time the whole kernel under each formulation")
    ap.add_argument("--only", default="", help="comma list of formulations")
    args = ap.parse_args()
    dev = jax.devices()[0]
    if not args.check and dev.platform != "tpu":
        sys.exit("prefix_microbench: no TPU; a CPU time is not a speed "
                 "(--check runs the exactness half)")
    rng = np.random.default_rng(28)
    only = set(filter(None, args.only.split(",")))
    if args.kernel:
        kernel_times(only)
        print(json.dumps({"device": {"platform": dev.platform,
                                     "kind": dev.device_kind}}))
        return
    for w in (1024, 4096):
        onehot_rows = (
            rng.integers(0, CLASSES, size=(STEPS, w))[..., None]
            == np.arange(CLASSES)
        ).astype(np.int32)  # (S, W, C)
        feeds = {0: jnp.asarray(onehot_rows),
                 -1: jnp.asarray(onehot_rows.transpose(0, 2, 1))}
        x0 = rng.integers(0, 2**30, size=(w, CLASSES)).astype(np.int32)
        cap0 = rng.integers(0, 64, size=(w,)).astype(np.int32)
        for axis, layout in ((0, "rows"), (-1, "lanes")):
            xin = jnp.asarray(x0 if axis == 0 else x0.T)
            if not args.check:
                print(json.dumps({
                    "formulation": "null_body", "layout": layout, "w": w,
                    "prefix_us": round(timed(scan_null(axis), xin,
                                             feeds[axis]), 3),
                }), flush=True)
            for name, prefix in candidates().items():
                if only and name not in only:
                    continue
                line = {"formulation": name, "layout": layout, "w": w}
                try:
                    line["exact"] = exact(prefix, axis, w, rng)
                    if not args.check:
                        line["prefix_us"] = round(timed(
                            scan_prefix(prefix, axis), xin, feeds[axis]), 3)
                        line["fill_us"] = round(timed(
                            scan_fill(prefix, axis), jnp.asarray(cap0),
                            feeds[axis]), 3)
                except Exception as e:  # noqa: BLE001 - a formulation the
                    # compiler refuses is a row of the table, not a crash
                    line["error"] = f"{type(e).__name__}: {e}"[:300]
                print(json.dumps(line), flush=True)
    print(json.dumps({"device": {"platform": dev.platform,
                                 "kind": dev.device_kind}}))


if __name__ == "__main__":
    main()
