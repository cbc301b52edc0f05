"""Deviceless v5e compiles of the Pallas group-by kernel and of the engine's
real programs around it.

The TPU compiler is installed in the sandbox and compiles for a chip that
is described and not attached; nothing runs.  These are what guard the
kernel now that no engine path gives way to XLA when Mosaic refuses it.

The topology is described inside a module-scoped fixture — never at
import — so every xdist worker collects the same tests and only the
worker that runs this file loads the TPU library.
"""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax
from jax.sharding import NamedSharding, PartitionSpec as P, SingleDeviceSharding

from spark_druid_olap_tpu.ops.pallas_groupby import pallas_partial_aggregate

R_KERNEL = 1 << 20  # rows of one bare-kernel call
R_SEGMENT = 1 << 19  # rows of one SSB segment (ssb.register_streamed)
ARENA_BLOCKS = 8


def _arena_stack(blocks):
    """Shape of a one-chip arena stack (`exec/arena.py stacked_cols`)."""
    return (blocks, R_SEGMENT // 128, 128)


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc

    try:
        desc = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a deviceless compile can be written to the persistent cache but not
    # read back without a chip: keep the cache out of these compiles
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _kernel_args(lead, G, Ms, Mn, Mx, sharding):
    return (
        _spec(lead, jnp.int32, sharding),
        _spec(lead, jnp.bool_, sharding),
        _spec(lead + (Ms,), jnp.float32, sharding),
        _spec(lead + (Mn + Mx,), jnp.float32, sharding),
        _spec(lead + (Mn + Mx,), jnp.bool_, sharding),
    )


def _assert_kernel(compiled):
    assert "tpu_custom_call" in compiled.as_text()


def _assert_lane_dense(text):
    """A compiled program hands the kernel lane-dense rows: no line
    defines a per-row integer or predicate array as a column (`s32[R,1]`
    or `pred[R,1]` with the unit dimension minor: 128 x padding in (8,
    128) tiles, what the kernel's operands were before PR 28), by any
    operation (`copy`, `broadcast`, `fusion`, `bitcast`) under any scope,
    and no `%copy` relays a whole per-row array out on the way to the
    kernel.  A per-row dimension has five digits or more (a segment is
    2^19 rows); no group count here has."""
    names = lambda pattern: [
        ln.split(" = ")[0].strip() for ln in text.splitlines()
        if re.search(pattern, ln)
    ]
    assert not names(r"= (s32|pred)\[\d{5,},1\]\{1,0")
    assert not names(r"= (s32|pred|f32|bf16)\[(\d+,)?\d{5,}(,\d+)?\]\S* copy\(")


def _kernel_calls(text):
    """(operand names, operand shapes) of every `tpu_custom_call`."""
    calls = []
    for ln in text.splitlines():
        if 'custom_call_target="tpu_custom_call"' not in ln:
            continue
        names = re.search(r" custom-call\(([^)]*)\)", ln).group(1)
        shapes = re.search(r"operand_layout_constraints=\{(.*?\})\}", ln)
        calls.append((
            names.split(", "),
            re.findall(r"(\w+\[[\d,]*\])\{", shapes.group(1)),
        ))
    return calls


def _assert_operands_made_in_vmem(text, values, rows=R_SEGMENT, minmax=0):
    """What ISSUE 36 took out of the programs: the kernel's value parts,
    its count column and its mask multiply are made in VMEM, so in front
    of every `tpu_custom_call` there is no `reduce-precision` fusion, no
    bfloat16 per-row array, no fusion, concatenate or pad that writes a
    stack of per-row rows, and the call's operands are the id row and
    one dense `[rows / 128, 128]` view a sum VALUE: none for a count
    (`__rows`), none for an aggregation class the query has not."""
    assert " reduce-precision(" not in text
    assert not re.search(r"bf16\[(\d+,)?\d{5,}", text)
    stacked = [
        ln.split(" = ")[0].strip() for ln in text.splitlines()
        if re.search(
            r"= (f32|bf16)\[([2-9]|\d\d+),\d{5,}\]\S* "
            r"(fusion|concatenate|pad)\(", ln
        )
    ]
    assert not stacked, stacked
    calls = _kernel_calls(text)
    assert calls
    for names, shapes in calls:
        assert len(names) == len(shapes) == 1 + values + minmax, (names, shapes)
        assert shapes[0] == f"s32[1,{rows}]", shapes
        assert all(
            re.fullmatch(rf"(f32|s32)\[{rows // 128},128\]", sh)
            for sh in shapes[1:1 + values]
        ), shapes


def _sum_values(lowering):
    """Sum columns of a lowered query that bring the kernel an operand:
    all but the hidden `__rows` count (an unfiltered COUNT(*) aliases it
    and has no column of its own)."""
    return len(lowering.la.sum_names) - 1


# (G, Ms, Mn, Mx): SSB q1 (one group), TPC-H Q1, a min/max mix at the
# 1024-group tile edge, the widest single tile, q2's 8008 (two tiles)
KERNEL_SHAPES = [
    (1, 1, 0, 0),
    (12, 4, 0, 0),
    (1024, 3, 1, 1),
    (4096, 2, 0, 0),
    (8008, 1, 0, 0),
]


@pytest.mark.parametrize("G,Ms,Mn,Mx", KERNEL_SHAPES)
def test_kernel_compiles_for_v5e(one_chip, G, Ms, Mn, Mx):
    compiled = pallas_partial_aggregate.lower(
        *_kernel_args((R_KERNEL,), G, Ms, Mn, Mx, one_chip),
        num_groups=G, num_min=Mn, num_max=Mx,
    ).compile()
    _assert_kernel(compiled)
    text = compiled.as_text()
    _assert_lane_dense(text)
    # the other kernels' `[R, Ms]` array: every column a value operand.
    # Mosaic legalized the split made in VMEM (bitcasts and a mask of the
    # word: nothing XLA's TPU pipeline can elide as excess precision, as
    # it did a convert to bf16 and back, PR 28)
    _assert_operands_made_in_vmem(
        text, values=Ms, rows=R_KERNEL, minmax=(Mn > 0) + (Mx > 0)
    )


# the lowering's form under strategy="pallas": (value dtypes, counts)
ROWS_FORMS = {
    "count_only": ((), 1),
    "bare_f32_and_count": ((jnp.float32,), 1),
    "bare_int32_and_count": ((jnp.int32,), 1),
    "two_values_two_counts": ((jnp.float32, jnp.int32), 2),
}


@pytest.mark.parametrize("form", sorted(ROWS_FORMS))
@pytest.mark.parametrize("G", [1, 800])
def test_kernel_takes_unmasked_rows_for_v5e(one_chip, G, form):
    """`row_arrays(strategy="pallas")`'s form: one `[R]` row a value,
    float32 or int32 as the column is stored, `None` for a count.  The
    dense view of a row is a bitcast: no fusion stands between the
    parameter and the kernel."""
    dtypes, counts = ROWS_FORMS[form]
    gid, mask, _, mmv, mmm = _kernel_args((R_KERNEL,), G, 1, 0, 0, one_chip)
    rows = (None,) * counts + tuple(
        _spec((R_KERNEL,), dt, one_chip) for dt in dtypes
    )
    text = pallas_partial_aggregate.lower(
        gid, mask, rows, mmv, mmm, num_groups=G, num_min=0, num_max=0,
    ).compile().as_text()
    _assert_lane_dense(text)
    _assert_operands_made_in_vmem(text, values=len(dtypes), rows=R_KERNEL)
    fusions = [
        ln for ln in text.splitlines() if re.search(r"= \S+ fusion\(", ln)
        and re.search(r"= (f32|s32)\[\d{5,}", ln)
    ]
    # the one fusion a segment: the id row, -1 where masked
    assert len(fusions) == 1 and "s32[" in fusions[0], fusions


def test_kernel_compiles_at_the_mesh_shape_of_use(one_chip):
    """The mesh's dense-state call (`parallel/distributed.py`): the kernel
    once over a shard's whole rows, 29 SF10 segments at G' = 800, with no
    column operand and no relayout in front of it."""
    compiled = pallas_partial_aggregate.lower(
        *_kernel_args((29 * R_SEGMENT,), 800, 3, 0, 0, one_chip),
        num_groups=800, num_min=0, num_max=0,
    ).compile()
    _assert_kernel(compiled)
    _assert_lane_dense(compiled.as_text())
    _assert_operands_made_in_vmem(
        compiled.as_text(), values=3, rows=29 * R_SEGMENT
    )


SCAN_G, SCAN_MS = 1024, 2


@jax.jit
def _scan_fold(gid, mask, sv, mmv, mmm):
    def body(acc, xs):
        s, _, _ = pallas_partial_aggregate(
            *xs, num_groups=SCAN_G, num_min=0, num_max=0
        )
        return acc + s, None

    acc, _ = lax.scan(
        body, jnp.zeros((SCAN_G, SCAN_MS), jnp.float32),
        (gid, mask, sv, mmv, mmm),
    )
    return acc


def test_kernel_compiles_inside_scan(one_chip):
    """The arena's shape of use: the kernel as the body of a `lax.scan`
    over stacked `[B, R]` segment blocks."""
    compiled = _scan_fold.lower(
        *_kernel_args(
            (ARENA_BLOCKS, R_SEGMENT), SCAN_G, SCAN_MS, 0, 0, one_chip
        )
    ).compile()
    _assert_kernel(compiled)


@pytest.fixture(scope="module")
def ssb_ctx():
    """SSB with the dimension tables at SF1 — every dictionary is already
    at the cardinality it has at SF10, so the lowerings (and G) are the
    real ones — over a fact small enough to build in a test."""
    import spark_druid_olap_tpu as sd
    from spark_druid_olap_tpu.workloads import ssb

    ctx = sd.TPUOlapContext()
    ssb.register(ctx, tables=ssb.gen_tables(1.0, seed=7, fact_rows=4096))
    return ctx


@pytest.fixture(scope="module")
def ssb_dim_tables():
    """The dimension tables `ssb_ctx` registered (same seed, same draw)."""
    from spark_druid_olap_tpu.workloads import ssb

    return ssb.gen_dim_tables(1.0, np.random.default_rng(7))


def _lowered_query(ctx, name):
    from spark_druid_olap_tpu.exec.lowering import lower_groupby
    from spark_druid_olap_tpu.sql.parser import parse_sql
    from spark_druid_olap_tpu.workloads import ssb

    lp, _, _ = parse_sql(ssb.QUERIES[name])
    rw = ctx._planner().plan(lp)
    ds = ctx.catalog.get(rw.datasource)
    return rw.query, ds, lower_groupby(rw.query, ds)


def _segment_col_specs(ctx, ds, names, lead, sharding):
    """The engine's own per-segment device columns, as shapes of a real
    SSB segment on the described chip."""
    cols = ctx.engine._cols_for_segment(ds.segments[0], ds, list(names))
    return {
        n: _spec(lead, a.dtype, sharding) for n, a in cols.items()
    }


def _compiled_arena_text(ctx, ds, lowering, program, sharding):
    """`program` (an arena scan over `lowering`) compiled for the described
    chip at stacks of `ARENA_BLOCKS` segments: the compiler's HLO text."""
    from spark_druid_olap_tpu.exec import arena

    cols = _segment_col_specs(
        ctx, ds, lowering.columns, _arena_stack(ARENA_BLOCKS), sharding
    )
    carry = jax.tree.map(
        lambda a: _spec(a.shape, a.dtype, sharding),
        (arena._member_init(lowering),),
    )
    return program.lower(
        carry, cols,
        _spec((ARENA_BLOCKS,), jnp.bool_, sharding),
        _spec((ARENA_BLOCKS, 1), jnp.bool_, sharding),
    ).compile().as_text()


@pytest.fixture
def pallas_on(monkeypatch):
    """The engine asks `pallas_available()` (a live-backend question) to
    choose between the compiled kernel and interpret mode; under a
    deviceless compile the live backend is the CPU, so steer it here."""
    from spark_druid_olap_tpu.ops import pallas_groupby

    monkeypatch.setattr(pallas_groupby, "pallas_available", lambda: True)


# q1_1: G=1; q4_1: G=208 — the dense class the chooser turns into the
# kernel on a TPU (plan.cost.concrete_kernel)
@pytest.mark.parametrize("name,G", [("q1_1", 1), ("q4_1", 208)])
def test_engine_segment_program_compiles(one_chip, ssb_ctx, pallas_on, name, G):
    from spark_druid_olap_tpu.exec.engine import Engine

    q, ds, lowering = _lowered_query(ssb_ctx, name)
    assert lowering.num_groups == G
    from spark_druid_olap_tpu.plan.cost import concrete_kernel

    assert concrete_kernel("dense", G) == "pallas"
    eng = Engine(strategy="pallas")
    seg_fn = eng._segment_program(q, ds, lowering)
    cols = _segment_col_specs(
        ssb_ctx, ds, lowering.columns, (R_SEGMENT,), one_chip
    )
    text = seg_fn.lower([cols, cols]).compile().as_text()
    # flight1's request (q1_1) is this program: one kernel call a segment
    assert len(_kernel_calls(text)) == 2
    _assert_lane_dense(text)
    _assert_operands_made_in_vmem(text, values=_sum_values(lowering))


@pytest.mark.parametrize("name", ["q1_1", "q4_1"])
def test_engine_arena_scan_compiles(one_chip, ssb_ctx, pallas_on, name):
    """The one-dispatch arena program Engine(strategy="pallas") builds:
    the scanned fold over `[B, R]` stacks."""
    from spark_druid_olap_tpu.exec.engine import Engine

    q, ds, lowering = _lowered_query(ssb_ctx, name)
    program = Engine(strategy="pallas")._arena_program(
        q, ds, lowering, "pallas"
    )
    text = _compiled_arena_text(ssb_ctx, ds, lowering, program, one_chip)
    assert "tpu_custom_call" in text
    _assert_lane_dense(text)
    _assert_operands_made_in_vmem(text, values=_sum_values(lowering))


# q4_2's scope at SF10: 34 segments, two dispatch batches (32 + 2)
TWO_BATCH_BLOCKS = 34


@pytest.mark.parametrize("name", ["q1_1", "q4_1"])
def test_engine_arena_whole_form_compiles(one_chip, ssb_ctx, pallas_on, name):
    """The served default's one device call (ISSUE 33): the scan over a
    two-batch scope with its zero carry made and its last batch flushed
    inside the program — no carry argument, one `while`, the kernel in
    its body, and still no operand relaid out in front of it."""
    from spark_druid_olap_tpu.exec.engine import Engine

    q, ds, lowering = _lowered_query(ssb_ctx, name)
    program = Engine(strategy="pallas")._arena_program(
        q, ds, lowering, "pallas"
    )
    cols = _segment_col_specs(
        ssb_ctx, ds, lowering.columns, _arena_stack(TWO_BATCH_BLOCKS),
        one_chip,
    )
    compiled = program.lower(
        None, cols, _spec((TWO_BATCH_BLOCKS,), jnp.bool_, one_chip), None,
        init=True, finish=True,
    ).compile()
    # the finished state of the one member: sums, mins, maxs, live
    (out,) = compiled.out_info
    assert [o.shape for o in out][:1] == [
        (lowering.num_groups, len(lowering.la.sum_names))
    ] and len(out) == 4
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    assert len(re.findall(r" while\(", text)) == 1
    _assert_lane_dense(text)
    _assert_operands_made_in_vmem(text, values=_sum_values(lowering))


def test_adaptive_presence_program_compiles(one_chip, ssb_ctx, pallas_on):
    """q2_1 (G=8008, past the one-hot cutover) routes to the adaptive
    tier; its phase-A presence pass counts each grouping dim's codes
    with the kernel."""
    q, ds, lowering = _lowered_query(ssb_ctx, "q2_1")
    assert lowering.num_groups == 8008
    eng = ssb_ctx.engine
    seg_fn = eng._presence_program(q, ds, lowering)
    need = eng._presence_columns(q, lowering, ds)
    cols = _segment_col_specs(ssb_ctx, ds, need, (R_SEGMENT,), one_chip)
    text = seg_fn.lower([cols]).compile().as_text()
    # a presence count is the match tile's row sum: the id row alone
    assert len(_kernel_calls(text)) == len(lowering.dims)
    _assert_operands_made_in_vmem(text, values=0)


# the kept sets these queries' presence passes measure at SF10 (uniform
# keys: every code a dimension row can carry under the filter is present):
# (table, filter column, accepted values) per grouping dim, None = every
# code but the null slot
PHASE_B_KEPT = {
    "q2_1": {"p_brand1": ("part", "p_category", ["MFGR#12"])},
    "q3_2": {
        "c_city": ("customer", "c_nation", ["UNITED STATES"]),
        "s_city": ("supplier", "s_nation", ["UNITED STATES"]),
        "d_year": ("dwdate", "d_year", [1992, 1993, 1994, 1995, 1996, 1997]),
    },
    # s_nation keeps codes 1, 2, 3 (+2 more): the run whose shift is zero
    "q4_2": {
        "d_year": ("dwdate", "d_year", [1997, 1998]),
        "s_nation": ("supplier", "s_region", ["AMERICA"]),
        "p_category": ("part", "p_mfgr", ["MFGR#1", "MFGR#2"]),
    },
    # seven lane tiles: the widest phase B the cells run, the kernel's
    # since the dense class is priced on it (PR 30; the scatter's before)
    "q4_3": {
        "d_year": ("dwdate", "d_year", [1997, 1998]),
        "s_city": ("supplier", "s_nation", ["UNITED STATES"]),
        "p_brand1": ("part", "p_category", ["MFGR#14"]),
    },
}
# G' of each: the `compact_groups` its `adaptive_kept` span reads at SF10
PHASE_B_GROUPS = {"q2_1": 280, "q3_2": 600, "q4_2": 100, "q4_3": 800}


@pytest.mark.parametrize("name", sorted(PHASE_B_KEPT))
def test_adaptive_phase_b_relays_out_only_the_kernel_operands(
    one_chip, ssb_ctx, ssb_dim_tables, pallas_on, monkeypatch, name
):
    """The adaptive tier's phase-B arena program over the compacted
    lowering: the kept-code remap (`sdol.kept_remap`) stays fused in the
    group-id arithmetic, and the kernel takes the packed id as the dense
    row the fusion writes.  Before PR 28 its operands were `[R, 1]`
    columns, 128 x padded in (8, 128) tiles: two relayouts a segment in
    front of it (and, before PR 26, 27 more carried up into q2_1's
    remap).  Now none: no column operand is defined anywhere in the
    program and nothing is copied on the way to the kernel."""
    from spark_druid_olap_tpu.exec import adaptive_exec
    from spark_druid_olap_tpu.exec.engine import Engine

    # the cap on runs is the live backend's (the CPU's here): take the TPU's
    monkeypatch.setattr(adaptive_exec, "_compare_chain_max", lambda: 64)
    q, ds, lowering = _lowered_query(ssb_ctx, name)
    tables = ssb_dim_tables
    kept = []
    for d in lowering.dims:
        rule = PHASE_B_KEPT[name].get(d.spec.dimension)
        if rule is None:
            kept.append(np.arange(d.cardinality - 1, dtype=np.int32))
            continue
        table, column, accepted = rule
        rows = np.isin(tables[table][column], accepted)
        present = np.unique(tables[table][d.spec.dimension][rows])
        values = np.asarray(ds.dicts[d.spec.dimension].values)
        kept.append(np.flatnonzero(np.isin(values, present)).astype(np.int32))
    assert all(0 < len(k) < d.cardinality for k, d in zip(kept, lowering.dims))
    clow = adaptive_exec.compacted_lowering(lowering, kept)
    assert clow.num_groups == PHASE_B_GROUPS[name]
    program = Engine(strategy="pallas")._arena_program(
        q, ds, clow, "pallas", key_extra=("adaptive",)
    )
    text = _compiled_arena_text(ssb_ctx, ds, clow, program, one_chip)
    assert "tpu_custom_call" in text
    assert "sdol.kept_remap" in text
    _assert_lane_dense(text)
    _assert_operands_made_in_vmem(text, values=_sum_values(clow))


def test_spmd_arena_program_compiles_for_four_chips(topo, ssb_ctx, pallas_on):
    """The mesh path `chip_smoke.py --chips 4` drives: the shard_mapped
    arena scan plus its psum boundary merge, one program over a (4, 1)
    mesh of the described chips, at SF10's stacked shape."""
    from spark_druid_olap_tpu.parallel import spmd_arena
    from spark_druid_olap_tpu.parallel.mesh import DATA_AXIS, make_mesh

    mesh = make_mesh(n_data=4, devices=topo.devices)
    rows = NamedSharding(mesh, P(DATA_AXIS, None))
    steps = 29  # 115 SF10 segments over 4 row devices
    q, ds, lowering = _lowered_query(ssb_ctx, "q4_1")
    program = spmd_arena.build_spmd_arena_program(
        mesh, [lowering], ["pallas"], Lk=steps
    )
    cols = _segment_col_specs(
        ssb_ctx, ds, lowering.columns, (4 * steps, R_SEGMENT), rows
    )
    compiled = program.lower(
        cols,
        _spec((), jnp.int32, NamedSharding(mesh, P())),
        _spec((4 * steps, 1), jnp.bool_, rows),
    ).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text and "all-reduce" in text
    _assert_lane_dense(text)
    _assert_operands_made_in_vmem(text, values=_sum_values(lowering))
    # every collective the compiler kept names its scope: a four-chip
    # trace shows the boundary merge's `%all-reduce`s under it
    merges = [
        ln for ln in text.splitlines()
        if re.search(r"= \S+ all-reduce(-start)?\(", ln)
    ]
    assert merges and all("sdol.boundary_merge" in ln for ln in merges), merges


def test_mesh_dense_state_program_compiles_for_four_chips(
    topo, ssb_ctx, pallas_on
):
    """The mesh's phase B and its small-G queries
    (`DistributedEngine._spmd_fn`): the kernel once over a shard's whole
    rows under `shard_map`, q4_1 over 29 SF10 segments a chip, handed
    the lowering's unmasked rows like the one-chip programs."""
    from spark_druid_olap_tpu.parallel.distributed import DistributedEngine
    from spark_druid_olap_tpu.parallel.mesh import DATA_AXIS, make_mesh

    mesh = make_mesh(n_data=4, devices=topo.devices)
    local_rows = 29 * R_SEGMENT
    q, ds, lowering = _lowered_query(ssb_ctx, "q4_1")
    cols = _segment_col_specs(
        ssb_ctx, ds, lowering.columns, (4 * local_rows,),
        NamedSharding(mesh, P(DATA_AXIS)),
    )
    run = DistributedEngine(mesh=mesh, strategy="pallas")._spmd_fn(
        lowering, local_rows, ds, tuple(sorted(cols)), strategy="pallas"
    )
    text = run.lower(cols).compile().as_text()
    assert "all-reduce" in text
    _assert_lane_dense(text)
    _assert_operands_made_in_vmem(
        text, values=_sum_values(lowering), rows=local_rows
    )


def test_compiled_programs_keep_device_scopes(one_chip, ssb_ctx, pallas_on):
    """The scopes (`obs.SCOPE_*`) survive the TPU compiler: the compiled
    arena program's operations carry them in their `op_name` metadata,
    which is what a profiler trace shows for each device operation."""
    from spark_druid_olap_tpu.exec.engine import Engine

    q, ds, lowering = _lowered_query(ssb_ctx, "q4_1")
    program = Engine(strategy="pallas")._arena_program(
        q, ds, lowering, "pallas"
    )
    text = _compiled_arena_text(ssb_ctx, ds, lowering, program, one_chip)
    op_names = set(re.findall(r'op_name="([^"]*)"', text))
    for scope in ("sdol.arena_scan", "sdol.filter", "sdol.group_keys",
                  "sdol.partial_agg", "sdol.carry_merge"):
        assert any(scope in n for n in op_names), scope
    # the kernel call sits under the scan and the kernel's own scope
    kernel = [n for n in op_names if "pallas_partial_aggregate" in n]
    assert kernel and all(
        "sdol.arena_scan" in n and "sdol.partial_agg" in n for n in kernel
    )
