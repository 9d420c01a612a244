"""The chip's compiler, asked here: the programs of the main path compile for
a described (not attached) TPU v5e at the widths production runs them.

Nothing executes, so these say nothing of results or times — only that the
TPU compiler accepts each program and that it fits the chip.  The topology
is described inside a fixture, never at import: only one process at a time
may load the TPU library, and every xdist worker imports this file.
"""

import numpy as np
import pytest

W, R, B, V, M, G = 1024, 8, 256, 2, 4, 4
W_SHARDED = 16384
HBM_BYTES = 16 * 2**30  # one v5e chip


@pytest.fixture(scope="module")
def topo():
    import os

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # noqa: BLE001 - any failure means "cannot ask"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def no_cache():
    """A compile for a described chip is written to a persistent cache but
    cannot be read back without the chip; keep these out of any cache."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache

    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", before)
    compilation_cache.reset_cache()


def _kernel_shapes(w, put, extras):
    """ShapeDtypeStructs of the cut-scan arguments at (w, R, B, V); `put`
    maps a sharding kind ("w2" (W, R) / "w1" (W,) / "rep" / "cm" (M, W))
    to the sharding of that argument."""
    import jax

    def s(shape, kind, dtype=np.int32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=put(kind))

    args = [
        s((w, R), "w2"), s((w,), "w1"), s((w,), "w1"),    # free nt lifetime
        s((B, V, R), "rep"), s((B,), "rep"), s((B, V), "rep"),
        s((M, w), "cm"), s((B, V), "rep"),                # class_m order_ids
    ]
    kwargs = {}
    if "all" in extras:
        kwargs["total"] = s((w, R), "w2")
        kwargs["all_mask"] = s((B, V, R), "rep")
    if "gang" in extras:
        kwargs["gang_nodes"] = s((B,), "rep")
        kwargs["gang_ok"] = s((w,), "w1")
        kwargs["group_onehot"] = s((w, G), "w2")
    if "resv" in extras:
        kwargs["gang_resv"] = s((w,), "w1")
    if "pmask" in extras:
        kwargs["policy_mask"] = s((B, w), "cm")
    return args, kwargs


def _assert_no_reduce_window(compiled):
    """The chip's compiler lowers a `cumsum` to a `reduce-window`, which
    cost the scan 12.5 us a step (86% of the kernel) until PR 28 wrote the
    prefixes as shifted adds: none anywhere in the program, so none in the
    scan's body.  A prefix that goes back to `cumsum` fails here, on the
    CPU, before any chip is asked."""
    text = compiled.as_text()
    assert text.count(" while(") >= 1  # the scan is there to look into
    assert text.count("reduce-window") == 0


@pytest.mark.parametrize(
    "extras", [(), ("all",), ("gang",), ("pmask",), ("gang", "resv")],
    ids=["flat", "all-mask", "gang", "policy-mask", "gang-reserved"],
)
def test_single_chip_kernel_compiles_for_v5e(topo, no_cache, extras):
    import jax
    from jax.sharding import SingleDeviceSharding

    from hyperqueue_tpu.ops.assign import greedy_cut_scan_impl

    one_chip = SingleDeviceSharding(topo.devices[0])
    args, kwargs = _kernel_shapes(W, lambda kind: one_chip, extras)
    compiled = jax.jit(
        greedy_cut_scan_impl, donate_argnums=(0, 1)
    ).lower(*args, **kwargs).compile()
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes + mem.argument_size_in_bytes < HBM_BYTES
    _assert_no_reduce_window(compiled)


def test_shared_cell_kernel_compiles_for_v5e(topo, no_cache):
    """`shared-1k.reserve`'s solve at its own extents (W 1 024, a bucket
    of 512 rows, 2 variants, 16 groups, the reservation codes): the loops
    that run each row's own work compile with no conditional, no
    `reduce-window`, and fit the chip."""
    import jax
    from jax.sharding import SingleDeviceSharding

    from hyperqueue_tpu.ops.assign import greedy_cut_scan_impl

    b, v, r, m, g = 512, 2, 4, 4, 16
    one_chip = SingleDeviceSharding(topo.devices[0])

    def s(*shape):
        return jax.ShapeDtypeStruct(shape, np.int32, sharding=one_chip)

    compiled = jax.jit(greedy_cut_scan_impl, donate_argnums=(0, 1)).lower(
        s(W, r), s(W), s(W), s(b, v, r), s(b), s(b, v), s(m, W), s(b, v),
        gang_nodes=s(b), gang_ok=s(W), group_onehot=s(W, g),
        gang_resv=s(W),
    ).compile()
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes + mem.argument_size_in_bytes < HBM_BYTES
    _assert_no_reduce_window(compiled)
    assert " conditional(" not in compiled.as_text()


def _unpack_shapes(layout, put):
    """ShapeDtypeStructs of the unpack program's arguments (ops/inputs.py):
    the resident state it consumes (none in the full form) and the
    (D, L) buffer; `put` as in `_kernel_shapes`."""
    import jax

    def s(shape, kind):
        return jax.ShapeDtypeStruct(shape, np.int32, sharding=put(kind))

    w = layout.state[0]
    state = () if layout.rows is None else tuple(
        s((w, width), "w2") if width else s((w,), "w1")
        for width in layout.widths
    )
    return state, s((layout.devices, layout.length), "w2")


def _tick_parts(w, gang, sharded):
    """(shape, kind) of the per-solve inputs a tick brings: the one-chip
    model's, or the mesh's with its batch table."""
    if sharded:
        table = B * V * R * 2 + B + 2 * B * V  # with the all-mask
        parts = [((table,), 2), ((M, w), 3)]
    else:
        parts = [((M, w), 3), ((B, V), 2), ((B, V, R), 2), ((B,), 2),
                 ((B, V), 2)]
    if gang:
        parts += [((B,), 2), ((w,), 1), ((w, G), 0)]
    return tuple(parts)


@pytest.mark.parametrize("gang", [False, True], ids=["flat", "gang"])
@pytest.mark.parametrize("rows", [16, 512, None], ids=["k16", "k512", "full"])
def test_unpack_inputs_compiles_for_v5e(topo, no_cache, rows, gang):
    """The program that turns a solve's one packed put into its inputs, in
    both forms: the delta's scatter into the donated resident arrays at
    the smallest and the largest row bucket of 1 024 workers, and the full
    form's slices."""
    import re

    from jax.sharding import SingleDeviceSharding

    from hyperqueue_tpu.ops.inputs import InputLayout, _unpacker

    one_chip = SingleDeviceSharding(topo.devices[0])
    layout = InputLayout(1, (W, R, False), rows, _tick_parts(W, gang, False))
    state, buf = _unpack_shapes(layout, lambda kind: one_chip)
    lowered = _unpacker().lower(state, buf, layout=layout, mesh=None)
    out = lowered.out_info
    assert [o.shape for o in out[:3]] == [(W, R), (W,), (W,)]
    assert [o.shape for o in out[3:]] == [shape for shape, _k in layout.parts]
    text = lowered.compile().as_text()
    assert not re.search(COLLECTIVES, text)
    assert ("scatter" in text) == (rows is not None)


def test_device_slicer_compiles_for_v5e(topo, no_cache):
    """The overflow fallback's slicer (ops/answer.live_slicer)."""
    import jax
    from jax.sharding import SingleDeviceSharding

    from hyperqueue_tpu.ops.answer import live_slicer

    one_chip = SingleDeviceSharding(topo.devices[0])
    counts = jax.ShapeDtypeStruct((B, V, W), np.int32, sharding=one_chip)
    live_slicer(200, 2, 1000).lower(counts).compile()  # live extents


COLLECTIVES = r"all-gather|all-reduce|collective-permute|all-to-all"


def _packer_shapes(w, put):
    """ShapeDtypeStructs of the packer's arguments, the kernel's three
    outputs at (B, V, w, R); `put` as in `_kernel_shapes`."""
    import jax

    def s(shape, kind):
        return jax.ShapeDtypeStruct(shape, np.int32, sharding=put(kind))

    return s((B, V, w), "counts"), s((w, R), "w2"), s((w,), "w1")


@pytest.mark.parametrize(
    "extents", [(200, 2, 1000), (1, 1, 1000)], ids=["compact", "dense-small"]
)
def test_answer_packer_compiles_for_v5e(topo, no_cache, extents):
    """The packing program behind the single-chip kernel, in both forms
    the extents choose: no `reduce-window` (its prefixes are shifted adds
    too), and the compact form one program a padded shape, whatever the
    live extents."""
    import re

    from jax.sharding import SingleDeviceSharding

    from hyperqueue_tpu.ops.answer import _packer, layout_for

    one_chip = SingleDeviceSharding(topo.devices[0])
    args = _packer_shapes(W, lambda kind: one_chip)
    layout = layout_for(extents, (B, V, W, R))
    assert (layout.rows is None) == (extents[0] > 1)
    compiled = _packer().lower(*args, mesh=None, rows=layout.rows).compile()
    text = compiled.as_text()
    assert not re.search(COLLECTIVES, text)
    assert text.count("reduce-window") == 0
    if layout.rows is None:
        assert layout_for((123, 2, 777), (B, V, W, R)).rows is None


def test_answer_packer_on_four_v5e_adds_no_collective(topo, no_cache):
    """On the mesh every chip compacts its own W-shard: the program the
    chip's compiler makes of the packer holds no collective (left to
    GSPMD, a compaction would all-gather 29 MB of counts) and, like the
    kernels since PR 28, no `reduce-window`; its output is one (4, L)
    buffer, a row a chip."""
    import re

    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from hyperqueue_tpu.ops.answer import _packer, layout_for

    mesh = Mesh(np.array(topo.devices[:4]), axis_names=("w",))
    specs = {"counts": P(None, None, "w"), "w2": P("w", None), "w1": P("w")}
    args = _packer_shapes(
        W_SHARDED, lambda kind: NamedSharding(mesh, specs[kind])
    )
    for extents in ((224, 2, W_SHARDED), (1, 1, W_SHARDED)):
        layout = layout_for(extents, (B, V, W_SHARDED, R), devices=4)
        lowered = _packer().lower(*args, mesh=mesh, rows=layout.rows)
        assert lowered.out_info.shape == (4, layout.length)
        text = lowered.compile().as_text()
        assert not re.search(COLLECTIVES, text)
        assert text.count("reduce-window") == 0


@pytest.mark.parametrize(
    "extras", [(), ("gang",), ("all",), ("gang", "resv")],
    ids=["flat", "gang", "all-mask", "gang-reserved"],
)
def test_sharded_kernel_compiles_for_four_v5e(topo, no_cache, extras):
    import re

    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from hyperqueue_tpu.parallel.solve import sharded_cut_scan_donate

    mesh = Mesh(np.array(topo.devices[:4]), axis_names=("w",))
    specs = {"w2": P("w", None), "w1": P("w"), "rep": P(),
             "cm": P(None, "w")}
    args, kwargs = _kernel_shapes(
        W_SHARDED, lambda kind: NamedSharding(mesh, specs[kind]), extras
    )
    # the resident solve takes needs/sizes/min_time/order_ids/all_mask as
    # one replicated vector (parallel/solve.pack_batch_table)
    import jax

    free, nt_free, lifetime, needs, sizes, min_time, class_m, order_ids = args
    packed = [needs, sizes, min_time, order_ids]
    has_all = "all_mask" in kwargs
    if has_all:
        packed.append(kwargs.pop("all_mask"))
    table = jax.ShapeDtypeStruct(
        (sum(int(np.prod(a.shape)) for a in packed),), np.int32,
        sharding=needs.sharding,
    )
    compiled = sharded_cut_scan_donate.lower(
        mesh, free, nt_free, lifetime, table, class_m,
        extents=needs.shape, has_all=has_all, **kwargs,
    ).compile()
    mem = compiled.memory_analysis()  # bytes per device
    assert mem.temp_size_in_bytes + mem.argument_size_in_bytes < HBM_BYTES
    assert re.search(r"all-gather|all-reduce", compiled.as_text())
    _assert_no_reduce_window(compiled)


@pytest.mark.parametrize("w", [8192, W_SHARDED], ids=["w8192", "w16384"])
def test_gang_shard_cell_programs_compile_for_four_v5e(topo, no_cache, w):
    """`gang-16k.campaign`'s solve at its own extents (96 rows in a bucket
    of 128, one variant, 256 groups) at both worker buckets its rows pass
    through: the sharded kernel with gang rows keeps both gathers and fits
    a chip, and the full-form unpack of its inputs (the (W, 256) one-hot in
    the buffer, a row a device) adds no collective."""
    import re

    import jax
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from hyperqueue_tpu.ops.inputs import InputLayout, _unpacker
    from hyperqueue_tpu.parallel.solve import sharded_cut_scan_donate

    b, v, r, m, g = 128, 1, 4, 4, 256
    mesh = Mesh(np.array(topo.devices[:4]), axis_names=("w",))
    specs = {"w2": P("w", None), "w1": P("w"), "rep": P(),
             "cm": P(None, "w")}

    def s(shape, kind):
        return jax.ShapeDtypeStruct(
            shape, np.int32, sharding=NamedSharding(mesh, specs[kind]))

    table = b * v * r + b + 2 * b * v
    compiled = sharded_cut_scan_donate.lower(
        mesh, s((w, r), "w2"), s((w,), "w1"), s((w,), "w1"),
        s((table,), "rep"), s((m, w), "cm"), extents=(b, v, r),
        gang_nodes=s((b,), "rep"), gang_ok=s((w,), "w1"),
        group_onehot=s((w, g), "w2"),
    ).compile()
    mem = compiled.memory_analysis()  # bytes per device
    assert mem.temp_size_in_bytes + mem.argument_size_in_bytes < HBM_BYTES
    text = compiled.as_text()
    assert len(re.findall(r"= \S+ all-(?:gather|reduce)(?:-start)?\(",
                          text)) >= 2
    _assert_no_reduce_window(compiled)
    parts = (((table,), 2), ((m, w), 3), ((b,), 2), ((w,), 1), ((w, g), 0))
    layout = InputLayout(4, (w, r, False), None, parts)
    state, buf = _unpack_shapes(
        layout, lambda kind: NamedSharding(mesh, specs[kind]))
    unpack = _unpacker().lower(state, buf, layout=layout, mesh=mesh).compile()
    assert not re.search(COLLECTIVES, unpack.as_text())


@pytest.mark.parametrize("rows", [2048, 4096, None],
                         ids=["k2048", "k4096", "full"])
def test_unpack_inputs_on_four_v5e_adds_no_collective(topo, no_cache, rows):
    """Around the sharded kernel at W = 16 384: the unpack program at the
    two row buckets a 16k cluster's churn meets and in the full form, with
    totals and gang inputs.  Every chip scatters the rows of its own shard
    out of its own row of the (4, L) buffer: the program the chip's
    compiler makes holds no collective, and its outputs carry the
    shardings the kernel takes them with."""
    import re

    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from hyperqueue_tpu.ops.inputs import InputLayout, _unpacker

    mesh = Mesh(np.array(topo.devices[:4]), axis_names=("w",))
    specs = {"w2": P("w", None), "w1": P("w"), "rep": P(),
             "cm": P(None, "w")}
    layout = InputLayout(
        4, (W_SHARDED, R, True), rows, _tick_parts(W_SHARDED, True, True))
    state, buf = _unpack_shapes(
        layout, lambda kind: NamedSharding(mesh, specs[kind]))
    lowered = _unpacker().lower(state, buf, layout=layout, mesh=mesh)
    compiled = lowered.compile()
    assert not re.search(COLLECTIVES, compiled.as_text())
    want = [specs["w2"], specs["w1"], specs["w1"], specs["w2"],
            specs["rep"], specs["cm"], specs["rep"], specs["w1"],
            specs["w2"]]
    for sharding, spec, info in zip(
            compiled.output_shardings, want, lowered.out_info):
        assert sharding.is_equivalent_to(
            NamedSharding(mesh, spec), len(info.shape))


def test_sharded_slicer_compiles_for_four_v5e(topo, no_cache):
    """The overflow fallback's slicer of the W-sharded counts."""
    import jax
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from hyperqueue_tpu.ops.answer import live_slicer

    mesh = Mesh(np.array(topo.devices[:4]), axis_names=("w",))
    counts = jax.ShapeDtypeStruct(
        (B, V, W_SHARDED), np.int32,
        sharding=NamedSharding(mesh, P(None, None, "w")))
    live_slicer(224, 2, W_SHARDED).lower(counts).compile()
