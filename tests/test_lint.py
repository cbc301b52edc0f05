"""Tier-1 gate for tools/graftlint — the AST static-analysis framework.

One consolidated suite (the former test_lint_v3.py acceptance file is
merged in; scaffolding lives in `lint_harness.py`), five layers:

1. **Fixture matrix** — every pass (including the project-aware
   semantic passes and the interprocedural GL24xx/GL25xx families) is
   exercised against >=2 violating and >=2 clean snippets, so the gate
   is self-testing: a pass that rots into a rubber stamp (or starts
   flagging idiomatic code) fails here, not in review.
2. **Repo gate** — `run_lint` over the real tree (the package, tests,
   tools/ AND bench.py) must be clean (no new findings, no stale
   baseline entries): this is the actual lint gate running under
   tier-1.  Includes the supersession guard: the baseline must stay
   empty of GL5xx/GL14xx lock entries now that GL25xx infers ownership.
3. **CLI contract** — `python -m tools.graftlint` exit codes, --json /
   --format {json,github}, --pass, --update-baseline (justification
   carry-over + diff summary), --changed (merge-base diff plus
   reverse-dependency closure), --profile, --stats.
4. **Resource/flow acceptance** (ex-v3) — dual-calibration golden,
   budget fallback chain, configurable call-through depth, constant
   propagation, whole-tree time budget.
5. **Wire-parity runtime anchor** — `exec/fallback.py`'s
   WIRE_AGG_FALLBACK registry (what the GL1002 pass checks
   structurally) actually maps every wire-decodable aggregator to a
   host function `_agg_one` implements.

Engine-layer unit tests (call graph, taint lattice, lock-ownership
inference, thread reachability) live in `test_lint_engine.py`.
"""

import json
import os
import time

import pytest

from lint_harness import (
    ROOT as _ROOT,
    TARGETS as _TARGETS,
    cli as _cli,
    eval_in as _eval_in,
    git_in as _git,
    project_of as _project_of,
    run_on,
    write_tree as _write_tree,
)
from tools.graftlint import (  # noqa: E402
    ALL_PASSES,
    LintConfigError,
    load_baseline,
    run_lint,
)


def _run_on(tmp_path, files, passes=None):
    return run_on(tmp_path, files, passes=passes)


# ---------------------------------------------------------------------------
# Fixture matrix: >=2 violating + >=2 clean snippets per pass
# ---------------------------------------------------------------------------

# miniature span-name registry the span-discipline fixtures resolve
# against (the real one is spark_druid_olap_tpu/obs/trace.py)
_OBS_TRACE_FIXTURE = """
    SPAN_H2D = "h2d"
    SPAN_FINALIZE = "finalize"
    SPAN_NAMES = frozenset({SPAN_H2D, SPAN_FINALIZE})
    SCOPE_BOUNDARY_MERGE = "sdol.boundary_merge"

    def span(name, **attrs):
        pass

    def device_scope(name):
        pass
"""

# pass -> (violating: [(files, expected_codes)], clean: [files])
_MATRIX = {
    "jit-cache": {
        "violating": [
            (
                {"pkg/serve.py": """
                    import jax

                    def handler(x):
                        f = jax.jit(lambda v: v + 1)
                        return f(x)
                """},
                {"GL101"},
            ),
            (
                {"pkg/serve.py": """
                    import jax

                    def build(self, q, shape):
                        @jax.jit
                        def prog(cols):
                            return cols

                        return prog
                """},
                {"GL101"},
            ),
            (
                {"pkg/keys.py": """
                    def program_for(self, q, shape):
                        key = f"{q}:{shape}"
                        return self._program_cache.get(key)
                """},
                {"GL103"},
            ),
            (
                {"pkg/spec.py": """
                    import jax

                    def build(f, nums):
                        return jax.jit(f, static_argnums=nums)
                """},
                {"GL101", "GL102"},
            ),
        ],
        "clean": [
            {"pkg/mod.py": """
                import functools

                import jax

                @jax.jit
                def f(x):
                    return x + 1

                @functools.partial(jax.jit, static_argnames=("n",))
                def g(x, n):
                    return x * n
            """},
            {"pkg/eng.py": """
                import jax

                class Engine:
                    def program(self, q, shape):
                        key = (q, shape)
                        fn = self._program_cache.get(key)
                        if fn is None:
                            fn = jax.jit(lambda v: v * 2)
                            self._program_cache[key] = fn
                        return fn
            """},
            # the calibration harness is excluded by pass config: it
            # deliberately rebuilds jits (compile time is what it measures)
            {"spark_druid_olap_tpu/plan/calibrate.py": """
                import jax

                def bench(x):
                    f = jax.jit(lambda v: v + 1)
                    return f(x)
            """},
        ],
    },
    "trace-purity": {
        "violating": [
            (
                {"pkg/traced.py": """
                    import time

                    import jax

                    @jax.jit
                    def f(x):
                        t = time.time()
                        return x + t
                """},
                {"GL202"},
            ),
            (
                {"pkg/traced.py": """
                    import jax
                    import numpy as np

                    @jax.jit
                    def g(x):
                        return np.asarray(x) + 1
                """},
                {"GL203"},
            ),
            (
                {"pkg/kern.py": """
                    import numpy as np

                    def _sum_kernel(x_ref, o_ref):
                        o_ref[:] = np.random.rand() + x_ref[:]
                """},
                {"GL202"},
            ),
            (
                {"spark_druid_olap_tpu/exec/engine.py": """
                    import jax

                    def resolve(batches):
                        out = []
                        for b in batches:
                            out.append(jax.device_get(b))
                        return out
                """},
                {"GL204"},
            ),
        ],
        "clean": [
            {"pkg/pure.py": """
                import jax
                import jax.numpy as jnp

                @jax.jit
                def f(x):
                    return jnp.sum(x * 2)
            """},
            # host code may sync freely outside loops / off the hot paths
            {"spark_druid_olap_tpu/exec/engine.py": """
                import jax

                def resolve(state):
                    sums, mins = jax.device_get(state)
                    return sums, mins
            """},
            {"pkg/host.py": """
                import time

                def timer_loop(items):
                    for it in items:
                        t0 = time.perf_counter()
                        work(it)
            """},
        ],
    },
    "dtype-x64": {
        "violating": [
            (
                {"pkg/wide.py": """
                    import jax.numpy as jnp

                    x = jnp.zeros(4, jnp.float64)
                """},
                {"GL301"},
            ),
            (
                {"pkg/weak.py": """
                    import jax
                    import jax.numpy as jnp

                    _POS = jnp.inf

                    @jax.jit
                    def f(m, v):
                        return jnp.where(m, v, _POS)
                """},
                {"GL303"},
            ),
            (
                {"pkg/strdtype.py": """
                    import jax.numpy as jnp

                    def widen(x):
                        return jnp.asarray(x, dtype="int64")
                """},
                {"GL302"},
            ),
        ],
        "clean": [
            # dtype COMPARISONS inspect width, they don't create it
            {"pkg/cmp.py": """
                import jax.numpy as jnp

                def is_wide(c):
                    return c.dtype == jnp.int64 or c.dtype in (jnp.float64,)
            """},
            {"pkg/matched.py": """
                import jax
                import jax.numpy as jnp

                @jax.jit
                def f(m, v):
                    return jnp.where(m, v, jnp.asarray(jnp.inf, dtype=v.dtype))
            """},
            # the pragma spelling documents a deliberate wide dtype
            {"pkg/time64.py": """
                import jax.numpy as jnp

                def widen_time(off, base):
                    # graftlint: disable=dtype-x64 -- time is int64 ms by contract
                    return base + off.astype(jnp.int64)
            """},
        ],
    },
    "compat-import": {
        "violating": [
            (
                {"pkg/direct.py": """
                    from jax.experimental.shard_map import shard_map
                """},
                {"GL401"},
            ),
            (
                {"pkg/flip.py": """
                    import jax

                    jax.config.update("jax_enable_x64", True)
                """},
                {"GL402"},
            ),
            (
                {"pkg/attr.py": """
                    import jax

                    def shim(fn, mesh, specs):
                        return jax.experimental.shard_map.shard_map(
                            fn, mesh=mesh, in_specs=specs, out_specs=specs
                        )
                """},
                {"GL401"},
            ),
        ],
        "clean": [
            # the kernel module owns the one scoped 32-bit trace
            {"spark_druid_olap_tpu/ops/pallas_groupby.py": """
                import jax

                def traced(fn):
                    with jax.enable_x64(False):
                        return fn()
            """},
            {"pkg/user.py": """
                import jax

                def build(fn, mesh, specs):
                    return jax.shard_map(
                        fn, mesh=mesh, in_specs=specs, out_specs=specs,
                        check_vma=False,
                    )
            """},
        ],
    },
    "lock-discipline": {
        "violating": [
            (
                {"pkg/breaker.py": """
                    import threading

                    class CircuitBreaker:
                        def __init__(self):
                            self._lock = threading.Lock()
                            self._state = "closed"

                        def trip(self):
                            self._state = "open"
                """},
                {"GL501"},
            ),
            (
                {"pkg/cachemod.py": """
                    import threading

                    class MetadataCache:
                        def __init__(self):
                            self._lock = threading.Lock()
                            self._tables = {}

                        def put(self, name, ds):
                            self._tables[name] = ds
                """},
                {"GL502"},
            ),
            (
                {"pkg/adm.py": """
                    import threading

                    class AdmissionController:
                        def __init__(self):
                            self._lock = threading.Lock()
                            self.admitted_total = 0

                        def acquire(self):
                            self.admitted_total += 1
                            return True
                """},
                {"GL501"},
            ),
        ],
        "clean": [
            {"pkg/locked.py": """
                import threading

                class CircuitBreaker:
                    def __init__(self):
                        self._lock = threading.Lock()
                        self._state = "closed"

                    def trip(self):
                        with self._lock:
                            self._state = "open"
            """},
            # unregistered classes keep their own conventions
            {"pkg/other.py": """
                class ScratchPad:
                    def __init__(self):
                        self._state = "x"

                    def set(self, v):
                        self._state = v
            """},
        ],
    },
    "pallas-shape": {
        "violating": [
            # index_map arity vs grid rank (GL701)
            (
                {"pkg/kern.py": """
                    import jax
                    import jax.numpy as jnp
                    from jax.experimental import pallas as pl

                    def _sum_kernel(x_ref, o_ref):
                        o_ref[:] = jnp.sum(x_ref[:])

                    def run(x):
                        return pl.pallas_call(
                            _sum_kernel,
                            grid=(4, 2),
                            in_specs=[
                                pl.BlockSpec((128, 8), lambda i: (i, 0)),
                            ],
                            out_specs=pl.BlockSpec(
                                (1, 1), lambda i, j: (0, 0)
                            ),
                            out_shape=jax.ShapeDtypeStruct(
                                (1, 1), jnp.float32
                            ),
                        )(x)
                """},
                {"GL701"},
            ),
            # kernel refs vs spec count, kernel in ANOTHER module (GL703)
            (
                {
                    "pkg/kern.py": """
                        import jax.numpy as jnp

                        def _fuse_kernel(a_ref, b_ref, o_ref):
                            o_ref[:] = a_ref[:] + b_ref[:]
                    """,
                    "pkg/call.py": """
                        import jax
                        import jax.numpy as jnp
                        from jax.experimental import pallas as pl

                        from .kern import _fuse_kernel

                        def run(a):
                            return pl.pallas_call(
                                _fuse_kernel,
                                grid=(4,),
                                in_specs=[
                                    pl.BlockSpec((128,), lambda i: (i,)),
                                ],
                                out_specs=pl.BlockSpec(
                                    (128,), lambda i: (i,)
                                ),
                                out_shape=jax.ShapeDtypeStruct(
                                    (512,), jnp.float32
                                ),
                            )(a)
                    """,
                },
                {"GL703"},
            ),
            # a scratch ref the kernel does not take (GL703)
            (
                {"pkg/kern.py": """
                    import jax
                    import jax.numpy as jnp
                    from jax.experimental import pallas as pl
                    from jax.experimental.pallas import tpu as pltpu

                    def _copy_kernel(x_ref, o_ref):
                        o_ref[:] = x_ref[:]

                    def run(x):
                        return pl.pallas_call(
                            _copy_kernel,
                            grid=(4,),
                            in_specs=[
                                pl.BlockSpec((8, 128), lambda i: (0, i)),
                            ],
                            out_specs=pl.BlockSpec(
                                (8, 128), lambda i: (0, i)
                            ),
                            out_shape=jax.ShapeDtypeStruct(
                                (8, 512), jnp.float32
                            ),
                            scratch_shapes=[
                                pltpu.VMEM((8, 128), jnp.float32),
                            ],
                        )(x)
                """},
                {"GL703"},
            ),
            # over-indexed ref + weak fill constant resolved through an
            # import (GL704, GL705)
            (
                {
                    "pkg/consts.py": """
                        import jax.numpy as jnp

                        POS = jnp.inf
                    """,
                    "pkg/kern.py": """
                        import jax
                        import jax.numpy as jnp
                        from jax.experimental import pallas as pl

                        from .consts import POS

                        def _min_kernel(x_ref, m_ref, o_ref):
                            w = jnp.where(m_ref[:] != 0, x_ref[:, 0], POS)
                            o_ref[0] = jnp.min(w)

                        def run(x, m):
                            return pl.pallas_call(
                                _min_kernel,
                                grid=(8,),
                                in_specs=[
                                    pl.BlockSpec((128,), lambda i: (i,)),
                                    pl.BlockSpec((128,), lambda i: (i,)),
                                ],
                                out_specs=pl.BlockSpec(
                                    (1,), lambda i: (0,)
                                ),
                                out_shape=jax.ShapeDtypeStruct(
                                    (1,), jnp.float32
                                ),
                            )(x, m)
                    """,
                },
                {"GL704", "GL705"},
            ),
        ],
        "clean": [
            # the real kernel's shape: partial-bound kwonly params, specs
            # and grid behind local names, dtype-matched fills
            {"pkg/kern.py": """
                import functools

                import jax
                import jax.numpy as jnp
                from jax.experimental import pallas as pl

                _POS = jnp.inf

                def _agg_kernel(x_ref, o_ref, *, block_g):
                    pos = jnp.asarray(_POS, dtype=o_ref.dtype)
                    w = jnp.where(x_ref[:] > 0, x_ref[:], pos)
                    o_ref[:] = o_ref[:] + jnp.sum(w, axis=0)

                def run(x, bg):
                    kernel = functools.partial(_agg_kernel, block_g=bg)
                    grid = (4, 2)
                    in_specs = [
                        pl.BlockSpec((128, 8), lambda j, i: (i, 0)),
                    ]
                    out_specs = pl.BlockSpec((8, 8), lambda j, i: (0, j))
                    return pl.pallas_call(
                        kernel,
                        grid=grid,
                        in_specs=in_specs,
                        out_specs=out_specs,
                        out_shape=jax.ShapeDtypeStruct((8, 8), jnp.float32),
                    )(x)
            """},
            # the kernel since ISSUE 36: one operand a value column, so
            # `*refs` and a starred run of specs (no static count: silent),
            # and scratch refs after the outputs
            {"pkg/var.py": """
                import functools

                import jax
                import jax.numpy as jnp
                from jax.experimental import pallas as pl
                from jax.experimental.pallas import tpu as pltpu

                def _rows_kernel(gid_ref, *refs, num_vals):
                    val_refs = refs[:num_vals]
                    out_ref, stack_ref = refs[num_vals:]
                    for k, ref in enumerate(val_refs):
                        stack_ref[k:k + 1, :] = ref[:]
                    out_ref[:] = stack_ref[:] * gid_ref[:]

                def _fixed_kernel(x_ref, o_ref, acc_ref):
                    acc_ref[:] = x_ref[:]
                    o_ref[:] = acc_ref[:]

                def run(gid, vals):
                    row = pl.BlockSpec((1, 128), lambda i: (0, i))
                    return pl.pallas_call(
                        functools.partial(_rows_kernel, num_vals=len(vals)),
                        grid=(4,),
                        in_specs=[row, *[row] * len(vals)],
                        out_specs=pl.BlockSpec((8, 128), lambda i: (0, i)),
                        out_shape=jax.ShapeDtypeStruct((8, 512), jnp.float32),
                        scratch_shapes=[pltpu.VMEM((8, 128), jnp.float32)],
                    )(gid, *vals)

                def run_fixed(x):
                    return pl.pallas_call(
                        _fixed_kernel,
                        grid=(4,),
                        in_specs=[pl.BlockSpec((8, 128), lambda i: (0, i))],
                        out_specs=pl.BlockSpec((8, 128), lambda i: (0, i)),
                        out_shape=jax.ShapeDtypeStruct((8, 512), jnp.float32),
                        scratch_shapes=[pltpu.VMEM((8, 128), jnp.float32)],
                    )(x)
            """},
            # dynamic everything: statically unresolvable is SILENT, not
            # a guess
            {"pkg/dyn.py": """
                from jax.experimental import pallas as pl

                def run(kernel, grid, specs, shapes):
                    return pl.pallas_call(
                        kernel, grid=grid, in_specs=specs,
                        out_specs=specs, out_shape=shapes,
                    )
            """},
        ],
    },
    "collective-axis": {
        "violating": [
            # collective over an axis no mesh declares (GL801)
            (
                {
                    "spark_druid_olap_tpu/parallel/mesh.py": """
                        DATA_AXIS = "data"
                        GROUPS_AXIS = "groups"
                    """,
                    "pkg/spmd.py": """
                        from jax import lax

                        def merge(x):
                            return lax.psum(x, "rows")
                    """,
                },
                {"GL801"},
            ),
            # PartitionSpec typo against Mesh(...)-declared axes (GL802)
            (
                {
                    "spark_druid_olap_tpu/parallel/mesh.py": """
                        import numpy as np
                        from jax.sharding import Mesh

                        def make(devs):
                            return Mesh(np.array(devs), ("data", "groups"))
                    """,
                    "pkg/spec.py": """
                        from jax.sharding import PartitionSpec as P

                        def specs():
                            return (P("data"), P("gruops"))
                    """,
                },
                {"GL802"},
            ),
            # axis smuggled through an imported constant (GL801)
            (
                {
                    "spark_druid_olap_tpu/parallel/mesh.py": """
                        DATA_AXIS = "data"
                    """,
                    "pkg/consts.py": """
                        MERGE_DIM = "merge"
                    """,
                    "pkg/col.py": """
                        from jax import lax

                        from .consts import MERGE_DIM

                        def merge(x):
                            return lax.pmax(x, MERGE_DIM)
                    """,
                },
                {"GL801"},
            ),
        ],
        "clean": [
            # the production shape: constants imported from the mesh
            # module, literal spellings of declared axes
            {
                "spark_druid_olap_tpu/parallel/mesh.py": """
                    DATA_AXIS = "data"
                    GROUPS_AXIS = "groups"
                """,
                "pkg/spmd.py": """
                    from jax import lax
                    from jax.sharding import PartitionSpec as P

                    from spark_druid_olap_tpu.parallel.mesh import DATA_AXIS

                    def merge(x):
                        return lax.psum(x, DATA_AXIS)

                    def specs():
                        return (P(DATA_AXIS), P("groups"), P())
                """,
            },
            # no mesh declaration in the scanned tree: absence of
            # evidence is not a finding
            {"pkg/solo.py": """
                from jax import lax

                def merge(x):
                    return lax.psum(x, "whatever")
            """},
            # axis tuple reached through an import: the tuple's element
            # names resolve against the module that WROTE them, so
            # "data" is a declared axis here
            {
                "pkg/axes.py": """
                    DAX = "data"
                    AXES = (DAX,)
                """,
                "pkg/meshmod.py": """
                    from jax.sharding import Mesh

                    from .axes import AXES

                    OTHER_AXIS = "groups"

                    def make(devs):
                        return Mesh(devs, AXES)
                """,
                "pkg/user.py": """
                    from jax import lax

                    def merge(x):
                        return lax.psum(x, "data")
                """,
            },
        ],
    },
    "checkpoint-coverage": {
        "violating": [
            # segment loop with no reachable checkpoint (GL901)
            (
                {"spark_druid_olap_tpu/exec/engine.py": """
                    def scan(segs, need):
                        out = []
                        for seg in segs:
                            out.append(fetch(seg, need))
                        return out
                """},
                {"GL901"},
            ),
            # call-through to a helper that does NOT checkpoint (GL901)
            (
                {"spark_druid_olap_tpu/exec/streaming.py": """
                    def _note(chunk):
                        return len(chunk)

                    def stream(chunks):
                        total = 0
                        for chunk in chunks:
                            total += _note(chunk)
                        return total
                """},
                {"GL901"},
            ),
        ],
        "clean": [
            # direct checkpoint in the loop body
            {"spark_druid_olap_tpu/exec/engine.py": """
                from ..resilience import checkpoint

                def scan(segs):
                    for seg in segs:
                        checkpoint("engine.segment_loop")
                        work(seg)
            """},
            # the flow layer: the checkpoint lives one call level down,
            # in a method resolved through the class
            {"spark_druid_olap_tpu/exec/sparse_exec.py": """
                from ..resilience import checkpoint

                class SparseExec:
                    def _dispatch_batch(self, batch):
                        checkpoint("sparse.segment_loop")
                        return run(batch)

                    def execute(self, batches):
                        out = []
                        for batch in batches:
                            out.append(self._dispatch_batch(batch))
                        return out
            """},
            # traced loops are exempt: a host checkpoint inside jit
            # would be wrong, not missing
            {"spark_druid_olap_tpu/exec/engine.py": """
                import jax

                @jax.jit
                def seg_fn(cols_batches):
                    state = None
                    for batch in cols_batches:
                        state = batch if state is None else state + batch
                    return state
            """},
            # loops without segment/chunk/rung vocabulary are not hot
            # units of work
            {"spark_druid_olap_tpu/exec/fallback.py": """
                def decode(names):
                    out = {}
                    for n in names:
                        out[n] = resolve(n)
                    return out
            """},
        ],
    },
    "wire-parity": {
        "violating": [
            # wire queryType whose model class the device dispatch never
            # handles (GL1001)
            (
                {
                    "spark_druid_olap_tpu/models/wire.py": """
                        from . import query as Q

                        def query_from_druid(d):
                            qt = d.get("queryType")
                            if qt == "groupBy":
                                return Q.GroupByQuery(datasource=d["d"])
                            if qt == "scan":
                                return Q.ScanQuery(datasource=d["d"])
                            raise ValueError(qt)
                    """,
                    "spark_druid_olap_tpu/exec/engine.py": """
                        from ..models import query as Q

                        class Engine:
                            def execute(self, q, ds):
                                if isinstance(q, Q.GroupByQuery):
                                    return self._gb(q, ds)
                                raise NotImplementedError
                    """,
                    "spark_druid_olap_tpu/server.py": """
                        from .models import query as Q

                        def druid_result_shape(q, df):
                            if isinstance(
                                q, (Q.GroupByQuery, Q.ScanQuery)
                            ):
                                return df
                            raise NotImplementedError
                    """,
                },
                {"GL1001"},
            ),
            # wire aggregator with no host-fallback translation (GL1002)
            (
                {
                    "spark_druid_olap_tpu/models/wire.py": """
                        from . import aggregations as A

                        def agg_from_druid(d):
                            t = d["type"]
                            simple = {"longSum": A.LongSum}
                            if t in simple:
                                return simple[t](d["name"], d["fieldName"])
                            if t == "hyperUnique":
                                return A.HyperUnique(d["name"], d["fieldName"])
                            raise ValueError(t)
                    """,
                    "spark_druid_olap_tpu/exec/lowering.py": """
                        from ..models import aggregations as A

                        def lower(agg):
                            if isinstance(agg, A.LongSum):
                                return "sum"
                            if isinstance(agg, A.HyperUnique):
                                return "hll"
                            raise NotImplementedError
                    """,
                    "spark_druid_olap_tpu/exec/fallback.py": """
                        from ..models import aggregations as A

                        WIRE_AGG_FALLBACK = {A.LongSum: "sum"}
                    """,
                },
                {"GL1002"},
            ),
        ],
        "clean": [
            # every registered class referenced by every surface
            {
                "spark_druid_olap_tpu/models/wire.py": """
                    from . import aggregations as A

                    def agg_from_druid(d):
                        t = d["type"]
                        simple = {"longSum": A.LongSum}
                        if t in simple:
                            return simple[t](d["name"], d["fieldName"])
                        if t == "hyperUnique":
                            return A.HyperUnique(d["name"], d["fieldName"])
                        raise ValueError(t)
                """,
                "spark_druid_olap_tpu/exec/lowering.py": """
                    from ..models import aggregations as A

                    def lower(agg):
                        if isinstance(agg, (A.LongSum, A.HyperUnique)):
                            return "ok"
                        raise NotImplementedError
                """,
                "spark_druid_olap_tpu/exec/fallback.py": """
                    from ..models import aggregations as A

                    WIRE_AGG_FALLBACK = {
                        A.LongSum: "sum",
                        A.HyperUnique: "approx_count_distinct",
                    }
                """,
            },
            # surfaces outside the scanned tree are skipped: a scoped
            # run proves nothing about absent files
            {"spark_druid_olap_tpu/models/wire.py": """
                from . import query as Q

                def query_from_druid(d):
                    if d.get("queryType") == "groupBy":
                        return Q.GroupByQuery(datasource=d["d"])
                    raise ValueError(d)
            """},
        ],
    },
    "error-discipline": {
        "violating": [
            (
                {"spark_druid_olap_tpu/server.py": """
                    def f():
                        try:
                            g()
                        except Exception:
                            pass
                """},
                {"GL601"},
            ),
            (
                {"spark_druid_olap_tpu/exec/eng.py": """
                    def f():
                        try:
                            g()
                        except BaseException:
                            y = 1
                """},
                {"GL601"},
            ),
        ],
        "clean": [
            {"spark_druid_olap_tpu/server.py": """
                def f():
                    try:
                        g()
                    except Exception:
                        raise

                def h():
                    try:
                        g()
                    except Exception:
                        log.warning("failed", exc_info=True)

                def k():
                    try:
                        g()
                    except Exception:  # fault-ok: best-effort probe
                        pass
            """},
            # outside the serving/execution layers broad excepts are the
            # caller's business — the pass is scoped
            {"spark_druid_olap_tpu/plan/opt.py": """
                def f():
                    try:
                        g()
                    except Exception:
                        pass
            """},
        ],
    },
    "span-discipline": {
        "violating": [
            # ad-hoc span name: a literal that is not in the registered
            # SPAN_* constant set fragments the trace taxonomy
            (
                {
                    "spark_druid_olap_tpu/obs/trace.py": _OBS_TRACE_FIXTURE,
                    "spark_druid_olap_tpu/exec/engine.py": """
                        from ..obs.trace import span

                        def run(batches):
                            for b in batches:
                                with span("warmup_phase"):
                                    dispatch(b)
                    """,
                },
                {"GL1101"},
            ),
            # dynamically-built span name: not statically resolvable, so
            # no consumer can match on it — the registry is the point
            (
                {
                    "spark_druid_olap_tpu/obs/trace.py": _OBS_TRACE_FIXTURE,
                    "spark_druid_olap_tpu/exec/engine.py": """
                        from ..obs.trace import span

                        def run(batches):
                            for i, b in enumerate(batches):
                                with span(f"segment-{i}"):
                                    dispatch(b)
                    """,
                },
                {"GL1101"},
            ),
            # a device scope the registry does not hold: no trace reader
            # (`tools/trace_scopes.py`) could find the collective by name
            (
                {
                    "spark_druid_olap_tpu/obs/trace.py": _OBS_TRACE_FIXTURE,
                    "spark_druid_olap_tpu/parallel/merge.py": """
                        from jax import lax

                        from ..obs.trace import device_scope
                        from .mesh import DATA_AXIS

                        def merge(state):
                            with device_scope("sdol.my_merge"):
                                return lax.psum(state, DATA_AXIS)
                    """,
                },
                {"GL1101"},
            ),
            # manually paired begin/end: the early `return` leaks an open
            # span — only the context manager owns the pairing
            (
                {
                    "spark_druid_olap_tpu/obs/trace.py": _OBS_TRACE_FIXTURE,
                    "spark_druid_olap_tpu/exec/engine.py": """
                        def run(tr, batches):
                            s = tr.start_span("h2d", None)
                            if not batches:
                                return None
                            out = [dispatch(b) for b in batches]
                            tr.end_span(s)
                            return out
                    """,
                },
                {"GL1102"},
            ),
        ],
        "clean": [
            # registered constant, resolved through the import alias
            {
                "spark_druid_olap_tpu/obs/trace.py": _OBS_TRACE_FIXTURE,
                "spark_druid_olap_tpu/exec/engine.py": """
                    from ..obs.trace import SPAN_H2D, span

                    def run(batches):
                        for b in batches:
                            with span(SPAN_H2D, batch=0):
                                dispatch(b)
                """,
            },
            # a literal spelling of a REGISTERED name also verifies
            {
                "spark_druid_olap_tpu/obs/trace.py": _OBS_TRACE_FIXTURE,
                "spark_druid_olap_tpu/exec/engine.py": """
                    from ..obs.trace import span

                    def run(batches):
                        with span("finalize"):
                            return [dispatch(b) for b in batches]
                """,
            },
            # the mesh's collectives under the registered scope constant
            {
                "spark_druid_olap_tpu/obs/trace.py": _OBS_TRACE_FIXTURE,
                "spark_druid_olap_tpu/parallel/merge.py": """
                    from jax import lax

                    from ..obs.trace import SCOPE_BOUNDARY_MERGE, device_scope
                    from .mesh import DATA_AXIS

                    def merge(state):
                        with device_scope(SCOPE_BOUNDARY_MERGE):
                            return lax.psum(state, DATA_AXIS)
                """,
            },
            # outside the instrumented surface the pass is silent (a
            # notebook-ish helper may name spans however it likes)
            {
                "spark_druid_olap_tpu/obs/trace.py": _OBS_TRACE_FIXTURE,
                "spark_druid_olap_tpu/plan/profile.py": """
                    from ..obs.trace import span

                    def probe():
                        with span("experimental-probe"):
                            pass
                """,
            },
        ],
    },
    "resource-budget": {
        "violating": [
            # tile set past the VMEM budget, shapes behind a module
            # constant (GL1201: 2 refs x 2048x2048 f32 = 32 MiB, x2
            # double-buffered = 64 MiB > the 16 MiB default budget)
            (
                {"pkg/kern.py": """
                    import jax
                    import jax.numpy as jnp
                    from jax.experimental import pallas as pl

                    BLOCK = 2048

                    def _sum_kernel(x_ref, o_ref):
                        o_ref[:] = x_ref[:] + 1.0

                    def run(x):
                        return pl.pallas_call(
                            _sum_kernel,
                            grid=(4,),
                            in_specs=[
                                pl.BlockSpec(
                                    (BLOCK, BLOCK), lambda i: (i, 0)
                                ),
                            ],
                            out_specs=pl.BlockSpec(
                                (BLOCK, BLOCK), lambda i: (i, 0)
                            ),
                            out_shape=jax.ShapeDtypeStruct(
                                (8192, 2048), jnp.float32
                            ),
                        )(x)
                """},
                {"GL1201"},
            ),
            # grid axis floor-divided to zero (GL1202): the constant
            # propagation resolves G // BG = 1024 // 4096 = 0
            (
                {"pkg/kern.py": """
                    import jax
                    import jax.numpy as jnp
                    from jax.experimental import pallas as pl

                    G = 1024
                    BG = 4096

                    def _k(x_ref, o_ref):
                        o_ref[:] = x_ref[:]

                    def run(x):
                        return pl.pallas_call(
                            _k,
                            grid=(G // BG, 4),
                            in_specs=[
                                pl.BlockSpec((128,), lambda i, j: (i,)),
                            ],
                            out_specs=pl.BlockSpec(
                                (128,), lambda i, j: (i,)
                            ),
                            out_shape=jax.ShapeDtypeStruct(
                                (512,), jnp.float32
                            ),
                        )(x)
                """},
                {"GL1202"},
            ),
            # block dimension arithmetic collapsing to zero (GL1203)
            (
                {"pkg/kern.py": """
                    import jax
                    import jax.numpy as jnp
                    from jax.experimental import pallas as pl

                    WIDTH = 1024

                    def _k(x_ref, o_ref):
                        o_ref[:] = x_ref[:]

                    def run(x):
                        return pl.pallas_call(
                            _k,
                            grid=(8,),
                            in_specs=[
                                pl.BlockSpec(
                                    (128, WIDTH - 1024), lambda i: (i, 0)
                                ),
                            ],
                            out_specs=pl.BlockSpec(
                                (128, 1), lambda i: (i, 0)
                            ),
                            out_shape=jax.ShapeDtypeStruct(
                                (1024, 1), jnp.float32
                            ),
                        )(x)
                """},
                {"GL1203"},
            ),
            # pltpu.VMEM scratch pushes an otherwise-fitting tile set
            # past the budget (ISSUE 6 satellite: scratch_shapes were
            # previously uncounted, so budgets under-reported).  Refs:
            # 2x(1024x1024x1B + 1024x1024x4B) = 10 MiB, under the
            # 16 MiB default; the 2048x1024 f32 scratch (8 MiB at 1x —
            # single allocation, not pipelined) tips it to 18 MiB.
            (
                {"pkg/kern.py": """
                    import jax
                    import jax.numpy as jnp
                    from jax.experimental import pallas as pl
                    from jax.experimental.pallas import tpu as pltpu

                    def _k(x_ref, o_ref, acc_ref):
                        o_ref[:] = x_ref[:]

                    def run(x):
                        return pl.pallas_call(
                            _k,
                            grid=(4,),
                            in_specs=[
                                pl.BlockSpec(
                                    (1024, 1024), lambda i: (i, 0)
                                ),
                            ],
                            out_specs=pl.BlockSpec(
                                (1024, 1024), lambda i: (i, 0)
                            ),
                            out_shape=jax.ShapeDtypeStruct(
                                (4096, 1024), jnp.float32
                            ),
                            scratch_shapes=[
                                pltpu.VMEM((2048, 1024), jnp.float32),
                            ],
                        )(x)
                """},
                {"GL1201"},
            ),
            # GL1204 upper-bound mode (the carried-over dynamically-
            # tuned gap): the block row count is runtime data, but
            # min(g, 4096) PROVES a 4096 bound — worst case
            # 2x(4096x2048x1B + 4096x2048x4B) = 80 MiB > 16 MiB, so the
            # tuning allows an over-budget tile even though GL1201's
            # exact resolution fails
            (
                {"pkg/kern.py": """
                    import jax
                    import jax.numpy as jnp
                    from jax.experimental import pallas as pl

                    def _k(x_ref, o_ref):
                        o_ref[:] = x_ref[:]

                    def run(x, g):
                        br = min(g, 4096)
                        return pl.pallas_call(
                            _k,
                            grid=(4,),
                            in_specs=[
                                pl.BlockSpec(
                                    (br, 2048), lambda i: (i, 0)
                                ),
                            ],
                            out_specs=pl.BlockSpec(
                                (br, 2048), lambda i: (i, 0)
                            ),
                            out_shape=jax.ShapeDtypeStruct(
                                (16384, 2048), jnp.float32
                            ),
                        )(x)
                """},
                {"GL1204"},
            ),
            # GL1204 through a min() with the bound as a module constant
            (
                {"pkg/kern.py": """
                    import jax
                    import jax.numpy as jnp
                    from jax.experimental import pallas as pl

                    MAX_BLOCK = 8192

                    def _k(x_ref, o_ref):
                        o_ref[:] = x_ref[:]

                    def run(x, rows):
                        return pl.pallas_call(
                            _k,
                            grid=(2,),
                            in_specs=[
                                pl.BlockSpec(
                                    (min(rows, MAX_BLOCK), 1024),
                                    lambda i: (i, 0),
                                ),
                            ],
                            out_specs=pl.BlockSpec(
                                (min(rows, MAX_BLOCK), 1024),
                                lambda i: (i, 0),
                            ),
                            out_shape=jax.ShapeDtypeStruct(
                                (16384, 1024), jnp.float32
                            ),
                        )(x)
                """},
                {"GL1204"},
            ),
        ],
        "clean": [
            # a dynamically-tuned kernel whose min() bound PROVABLY fits
            # the budget is clean in upper-bound mode: worst case
            # 2x(128x128x1B + 128x128x4B) is far under 16 MiB
            {"pkg/kern.py": """
                import jax
                import jax.numpy as jnp
                from jax.experimental import pallas as pl

                def _k(x_ref, o_ref):
                    o_ref[:] = x_ref[:]

                def run(x, g):
                    br = min(g, 128)
                    return pl.pallas_call(
                        _k,
                        grid=(8,),
                        in_specs=[
                            pl.BlockSpec((br, 128), lambda i: (i, 0)),
                        ],
                        out_specs=pl.BlockSpec((br, 128), lambda i: (i, 0)),
                        out_shape=jax.ShapeDtypeStruct(
                            (1024, 128), jnp.float32
                        ),
                    )(x)
            """},
            # modest tiles through min()/conditional arithmetic: the
            # evaluator proves them under budget
            {"pkg/kern.py": """
                import jax
                import jax.numpy as jnp
                from jax.experimental import pallas as pl

                def _k(x_ref, o_ref):
                    o_ref[:] = x_ref[:]

                def run(x):
                    br = min(1024, 512)
                    bg = 128 if br > 256 else 256
                    return pl.pallas_call(
                        _k,
                        grid=(8,),
                        in_specs=[
                            pl.BlockSpec((br, bg), lambda i: (i, 0)),
                        ],
                        out_specs=pl.BlockSpec((br, bg), lambda i: (i, 0)),
                        out_shape=jax.ShapeDtypeStruct(
                            (4096, 128), jnp.float32
                        ),
                    )(x)
            """},
            # dynamically-tuned shapes (parameters without defaults) are
            # unresolvable: silent, never guessed
            {"pkg/kern.py": """
                import jax
                import jax.numpy as jnp
                from jax.experimental import pallas as pl

                def _k(x_ref, o_ref):
                    o_ref[:] = x_ref[:]

                def run(x, block_rows, block_groups):
                    return pl.pallas_call(
                        _k,
                        grid=(4, 2),
                        in_specs=[
                            pl.BlockSpec(
                                (block_rows, block_groups),
                                lambda j, i: (i, 0),
                            ),
                        ],
                        out_specs=pl.BlockSpec(
                            (block_rows, block_groups),
                            lambda j, i: (0, j),
                        ),
                        out_shape=jax.ShapeDtypeStruct(
                            (4096, 4096), jnp.float32
                        ),
                    )(x)
            """},
            # small VMEM scratch within budget stays clean (the scratch
            # counts at 1x — it is a single allocation, not pipelined)
            {"pkg/kern.py": """
                import jax
                import jax.numpy as jnp
                from jax.experimental import pallas as pl
                from jax.experimental.pallas import tpu as pltpu

                def _k(x_ref, o_ref, acc_ref):
                    o_ref[:] = x_ref[:]

                def run(x):
                    return pl.pallas_call(
                        _k,
                        grid=(4,),
                        in_specs=[
                            pl.BlockSpec((256, 256), lambda i: (i, 0)),
                        ],
                        out_specs=pl.BlockSpec(
                            (256, 256), lambda i: (i, 0)
                        ),
                        out_shape=jax.ShapeDtypeStruct(
                            (1024, 256), jnp.float32
                        ),
                        scratch_shapes=[
                            pltpu.VMEM((256, 256), jnp.float32),
                        ],
                    )(x)
            """},
        ],
    },
    "jit-collision": {
        "violating": [
            # two key families for one cache with no distinguishing
            # literal: same arity, every position dyn-vs-dyn or
            # dyn-vs-lit (GL1301)
            (
                {"spark_druid_olap_tpu/exec/eng.py": """
                    class Engine:
                        def dense(self, q, shape, strategy):
                            key = (q, shape, strategy)
                            fn = self._program_cache.get(key)
                            if fn is None:
                                self._program_cache[key] = fn = object
                            return fn

                        def sparse(self, q, shape):
                            key = ("sparse", q, shape)
                            fn = self._program_cache.get(key)
                            if fn is None:
                                self._program_cache[key] = fn = object
                            return fn
                """},
                {"GL1301"},
            ),
            # per-call-unique key element: the cache never hits (GL1302)
            (
                {"spark_druid_olap_tpu/exec/eng.py": """
                    class Engine:
                        def program(self, q, ds):
                            key = (q, id(ds))
                            fn = self._program_cache.get(key)
                            if fn is None:
                                self._program_cache[key] = fn = object
                            return fn
                """},
                {"GL1302"},
            ),
            # the same function jit-wrapped twice across modules: two
            # compile caches for one program (GL1303)
            (
                {
                    "spark_druid_olap_tpu/ops/k.py": """
                        import jax

                        @jax.jit
                        def f(x):
                            return x + 1
                    """,
                    "spark_druid_olap_tpu/exec/use.py": """
                        import jax

                        from ..ops.k import f

                        g = jax.jit(f)
                    """,
                },
                {"GL1303"},
            ),
        ],
        "clean": [
            # tagged families over a shared structured-prefix builder:
            # the anchors pin alignment and the tags distinguish
            {"spark_druid_olap_tpu/exec/eng.py": """
                def _query_key(q, ds):
                    return (q, ds)

                class Engine:
                    def fused(self, q, ds, strategy):
                        key = _query_key(q, ds) + ("fused", strategy)
                        self._program_cache[key] = object
                        return key

                    def stream(self, q, ds, prep):
                        key = _query_key(q, ds) + ("stream", prep, 1)
                        self._program_cache[key] = object
                        return key
            """},
            # eviction loops and identical shared keys are not findings
            {"spark_druid_olap_tpu/exec/eng.py": """
                class Engine:
                    def put(self, seg_uid, name, arr):
                        key = (seg_uid, name)
                        self._device_cache[key] = arr

                    def get(self, seg_uid, name):
                        key = (seg_uid, name)
                        return self._device_cache.get(key)

                    def evict(self, base):
                        for k in [
                            k for k in self._device_cache
                            if k[:2] == base
                        ]:
                            self._device_cache.pop(k)
            """},
        ],
    },
    "lock-order": {
        "violating": [
            # ABBA cycle in one module, one side through a helper
            # (GL1401 at both edge sites)
            (
                {"spark_druid_olap_tpu/exec/locks.py": """
                    import threading

                    _A_LOCK = threading.Lock()
                    _B_LOCK = threading.Lock()

                    def a_then_b():
                        with _A_LOCK:
                            with _B_LOCK:
                                pass

                    def b_then_a():
                        with _B_LOCK:
                            _take_a()

                    def _take_a():
                        with _A_LOCK:
                            pass
                """},
                {"GL1401"},
            ),
            # cross-module cycle through DEPTH-2 call-through: the
            # breaker lock publishes into the registry lock, and a
            # registry render reaches back into the breaker two calls
            # down (GL1401)
            (
                {
                    "spark_druid_olap_tpu/obs/reg.py": """
                        import threading

                        REG_LOCK = threading.Lock()

                        def publish():
                            with REG_LOCK:
                                _note()

                        def _note():
                            from ..resilience import snap

                            snap()
                    """,
                    "spark_druid_olap_tpu/resilience.py": """
                        import threading

                        from .obs.reg import publish

                        BRK_LOCK = threading.Lock()

                        def record():
                            with BRK_LOCK:
                                publish()

                        def snap():
                            with BRK_LOCK:
                                pass
                    """,
                },
                {"GL1401"},
            ),
            # blocking sleep while the breaker lock is held (GL1402),
            # lexically and through a helper
            (
                {"spark_druid_olap_tpu/resilience.py": """
                    import threading
                    import time

                    class CircuitBreaker:
                        def __init__(self):
                            self._lock = threading.Lock()

                        def backoff(self):
                            with self._lock:
                                time.sleep(0.1)

                        def backoff_via_helper(self):
                            with self._lock:
                                self._wait()

                        def _wait(self):
                            time.sleep(0.1)
                """},
                {"GL1402"},
            ),
        ],
        "clean": [
            # a consistent hierarchy (A before B, never the reverse)
            {"spark_druid_olap_tpu/exec/locks.py": """
                import threading

                _A_LOCK = threading.Lock()
                _B_LOCK = threading.Lock()

                def a_then_b():
                    with _A_LOCK:
                        with _B_LOCK:
                            pass

                def also_a_then_b():
                    with _A_LOCK:
                        _take_b()

                def _take_b():
                    with _B_LOCK:
                        pass
            """},
            # reentrant self-acquisition (the RLock eviction idiom) and
            # sleeping AFTER the lock is released
            {"spark_druid_olap_tpu/utils/lru.py": """
                import threading
                import time

                class ByteBudgetCache:
                    def __init__(self):
                        self._lock = threading.RLock()

                    def __setitem__(self, key, v):
                        with self._lock:
                            self._evict()

                    def _evict(self):
                        with self._lock:
                            pass

                def backoff_outside(lock):
                    with lock:
                        pass
                    time.sleep(0.01)
            """},
        ],
    },
    "partial-discipline": {
        "violating": [
            # GL1601: partial=True flagged with NO coverage stamp and NO
            # publishing call
            (
                {"spark_druid_olap_tpu/exec/engine.py": """
                    class Engine:
                        def finish(self, m, pc):
                            if pc is not None and pc.is_partial:
                                m.partial = True
                            self.last_metrics = m
                """},
                {"GL1601"},
            ),
            # GL1602: except DeadlineExceeded swallowed into a generic
            # decline (neither re-raised nor absorbed into the collector)
            (
                {"spark_druid_olap_tpu/exec/sparse_exec.py": """
                    from ..resilience import DeadlineExceeded

                    def resolve(state):
                        try:
                            return state.fetch(), "ok"
                        except DeadlineExceeded:
                            return None, "error"
                """},
                {"GL1602"},
            ),
            # GL1601: coverage stamped but the partial observation is
            # never published (no record_* / span(SPAN_PARTIAL))
            (
                {"spark_druid_olap_tpu/api.py": """
                    def stamp(df, m, pc):
                        m.partial = True
                        m.coverage = pc.coverage()
                        return df
                """},
                {"GL1601"},
            ),
        ],
        "clean": [
            # partial=True + coverage + publication (record_query_metrics
            # reached lexically): the full contract
            {"spark_druid_olap_tpu/exec/engine.py": """
                from ..obs import record_query_metrics

                def finish(self, m, pc, outcome):
                    if pc is not None and pc.is_partial:
                        m.partial = True
                        m.coverage = pc.coverage()
                    record_query_metrics(m, outcome)
            """},
            # except DeadlineExceeded that re-raises, and one that absorbs
            # into the collector, are both disciplined
            {"spark_druid_olap_tpu/exec/adaptive_exec.py": """
                from ..resilience import DeadlineExceeded, current_partial

                def dispatch(q):
                    try:
                        return q.run()
                    except DeadlineExceeded:
                        raise

                def dispatch_soft(q):
                    try:
                        return q.run()
                    except DeadlineExceeded as err:
                        pc = current_partial()
                        if pc is None:
                            raise
                        pc.trigger(err.site)
                        return None
            """},
            # the same shapes OUTSIDE the executor/api scope belong to
            # other passes (the server's 504 conversion is legitimate)
            {"spark_druid_olap_tpu/server.py": """
                from .resilience import DeadlineExceeded

                def handle(self, body):
                    try:
                        return self.run(body)
                    except DeadlineExceeded as e:
                        return self.error(504, str(e))
            """},
        ],
    },
    "ingest-discipline": {
        "violating": [
            # GL1501: unlocked publish + unlocked guarded-field mutation
            (
                {"spark_druid_olap_tpu/ingest/delta.py": """
                    import threading

                    class IngestManager:
                        def __init__(self, catalog):
                            self.catalog = catalog
                            self._lock = threading.Lock()
                            self._buffers = {}

                        def buffer(self, name):
                            self._buffers[name] = object()
                            return self._buffers[name]

                        def append_rows(self, name, rows):
                            ds = self.catalog.get(name)
                            self.catalog.put(ds)
                """},
                {"GL1501"},
            ),
            # GL1502: a per-segment splice loop with no checkpoint, and
            # GL1503: direct mutation of catalog internals
            (
                {"spark_druid_olap_tpu/ingest/compact.py": """
                    class Compactor:
                        def __init__(self, catalog):
                            self.catalog = catalog

                        def compact(self, ds):
                            parts = []
                            for seg in ds.segments:
                                parts.append(seg.column("x"))
                            self.catalog._tables[ds.name] = ds
                """},
                {"GL1502", "GL1503"},
            ),
            # GL1503: object.__setattr__ on frozen catalog state
            (
                {"spark_druid_olap_tpu/ingest/delta.py": """
                    def splice(ds, segs):
                        object.__setattr__(ds, "segments", segs)
                        return ds
                """},
                {"GL1503"},
            ),
        ],
        "clean": [
            # locked publish, checkpointed loop, versioned put
            {"spark_druid_olap_tpu/ingest/delta.py": """
                import threading

                from ..resilience import checkpoint

                class IngestManager:
                    def __init__(self, catalog):
                        self.catalog = catalog
                        self._lock = threading.Lock()
                        self._buffers = {}

                    def buffer(self, name):
                        with self._lock:
                            self._buffers[name] = object()
                            return self._buffers[name]

                    def append_rows(self, name, rows):
                        buf = self.buffer(name)
                        with buf._lock:
                            ds = self.catalog.get(name)
                            for seg in ds.segments:
                                checkpoint("ingest.remap_segment")
                            self.catalog.put(ds)
            """},
            # the same shapes OUTSIDE the ingest tier are other passes'
            # business (lock-discipline/checkpoint-coverage own them)
            {"spark_druid_olap_tpu/catalog/other.py": """
                class Publisher:
                    def publish(self, catalog, ds):
                        for seg in ds.segments:
                            pass
                        catalog.put(ds)
            """},
        ],
    },
    "serving-discipline": {
        "violating": [
            # GL1701: raw subscript write into a result cache bypasses
            # the datasource-version stamp
            (
                {"spark_druid_olap_tpu/api.py": """
                    def execute(self, rw, df, rkey):
                        self._result_cache[rkey] = df.copy()
                        return df
                """},
                {"GL1701"},
            ),
            # GL1701: put() without the version keyword
            (
                {"spark_druid_olap_tpu/serve/core.py": """
                    def store(self, key, df, ds):
                        self.result_cache.put(key, df)
                """},
                {"GL1701"},
            ),
            # GL1702: fused demux publishes a member metrics object with
            # no query_id (assigned form)
            (
                {"spark_druid_olap_tpu/exec/engine.py": """
                    from ..obs import record_query_metrics
                    from .metrics import QueryMetrics

                    def execute_fused(self, queries, ds):
                        out = []
                        for q in queries:
                            m = QueryMetrics(query_type="groupBy")
                            record_query_metrics(m, "ok")
                            out.append(m)
                        return out
                """},
                {"GL1702"},
            ),
            # GL1702: inline construction published without query_id
            (
                {"spark_druid_olap_tpu/serve/fusion.py": """
                    from ..obs import record_query_metrics
                    from ..exec.metrics import QueryMetrics

                    def demux_fused(self, members):
                        for q in members:
                            record_query_metrics(
                                QueryMetrics(query_type="topN"), "ok"
                            )
                """},
                {"GL1702"},
            ),
        ],
        "clean": [
            # versioned put + query_id-stamped fused demux: the full
            # contract
            {"spark_druid_olap_tpu/serve/core.py": """
                def store(self, key, df, ds):
                    self.result_cache.put(
                        key, df, version=ds.version,
                        uids=frozenset(s.uid for s in ds.segments),
                    )
            """},
            {"spark_druid_olap_tpu/exec/engine.py": """
                from ..obs import record_query_metrics
                from .metrics import QueryMetrics

                def execute_fused(self, queries, ds, query_ids):
                    out = []
                    for q, qid in zip(queries, query_ids):
                        m = QueryMetrics(
                            query_type="groupBy", query_id=qid,
                        )
                        record_query_metrics(m, "ok")
                        out.append(m)
                    # an UNPUBLISHED scratch accumulator needs no id
                    batch_m = QueryMetrics(query_type="fused")
                    return out, batch_m
            """},
            # cache reads and non-cache subscripts are not writes; a
            # QueryMetrics outside fused scope belongs to other passes
            {"spark_druid_olap_tpu/serve/result_cache.py": """
                from ..obs import record_query_metrics
                from ..exec.metrics import QueryMetrics

                def lookup(self, key):
                    entry = self.result_cache.get(key)
                    self._stats["lookups"] = self._stats.get(
                        "lookups", 0
                    ) + 1
                    return entry

                def stamp_hit(self):
                    m = QueryMetrics(query_type="groupBy")
                    record_query_metrics(m, "ok")
            """},
        ],
    },
    "obs-discipline": {
        "violating": [
            # GL1801: bare block_until_ready in an executor module adds
            # an unconditional sync on every query
            (
                {"spark_druid_olap_tpu/exec/engine.py": """
                    import time
                    import jax

                    def dispatch(self, seg_fn, cols_list, m):
                        t0 = time.perf_counter()
                        out = seg_fn(cols_list)
                        jax.block_until_ready(out)
                        m.device_ms = (time.perf_counter() - t0) * 1e3
                        return out
                """},
                {"GL1801"},
            ),
            # GL1801: method-style sync on the result object, in the
            # mesh path
            (
                {"spark_druid_olap_tpu/parallel/distributed.py": """
                    def merge(self, run, cols):
                        state = run(cols)
                        state.block_until_ready()
                        return state
                """},
                {"GL1801"},
            ),
            # GL1802: a free-form datasource label published without the
            # cardinality guard
            (
                {"spark_druid_olap_tpu/obs/registry.py": """
                    def record_ingest(reg, datasource, rows):
                        reg.counter(
                            "x_total", "", labels=("datasource",)
                        ).labels(datasource=datasource).inc(rows)
                """},
                {"GL1802"},
            ),
            # GL1802: program family label from a raw variable
            (
                {"spark_druid_olap_tpu/obs/prof.py": """
                    def note(reg, family):
                        reg.counter(
                            "x_total", "", labels=("family",)
                        ).labels(family=family).inc()
                """},
                {"GL1802"},
            ),
        ],
        "clean": [
            # the sampling-gated helper is the one legitimate home of
            # block_until_ready — obs/ is outside the sync scope
            {"spark_druid_olap_tpu/obs/prof.py": """
                import jax

                def dispatch_sync(result, scope):
                    if scope is None or not scope.sampled:
                        return result
                    jax.block_until_ready(result)
                    return result
            """},
            # executors route through the helper; labels ride
            # bounded_label inline or via a same-function binding
            {"spark_druid_olap_tpu/exec/engine.py": """
                import time

                from ..obs import prof

                def dispatch(self, seg_fn, cols_list):
                    t0 = time.perf_counter()
                    out = seg_fn(cols_list)
                    return prof.dispatch_sync(out, t0)
            """},
            {"spark_druid_olap_tpu/obs/registry.py": """
                def record_ingest(reg, bounded_label, datasource, rows):
                    ds = bounded_label("ingest_datasource", datasource)
                    reg.counter(
                        "x_total", "", labels=("datasource", "outcome")
                    ).labels(datasource=ds, outcome="ok").inc(rows)
                    reg.counter(
                        "y_total", "", labels=("site",)
                    ).labels(
                        site=bounded_label("site", "engine.loop")
                    ).inc()
            """},
        ],
    },
    "transfer-discipline": {
        "violating": [
            # GL1901: bare device_put in the serving layer bypasses the
            # pipeline (no residency budget, fault site, or accounting)
            (
                {"spark_druid_olap_tpu/serve/fusion.py": """
                    import jax

                    def stage(self, seg, sharding):
                        return jax.device_put(seg.columns, sharding)
                """},
                {"GL1901"},
            ),
            # GL1902: jnp.asarray of host segment columns — direct call
            # args AND a same-function name binding
            (
                {"spark_druid_olap_tpu/exec/engine.py": """
                    import jax.numpy as jnp

                    def cols_for(self, seg, names):
                        out = {}
                        for n in names:
                            out[n] = jnp.asarray(seg.column(n))
                        out["__valid"] = jnp.asarray(seg.valid)
                        return out
                """},
                {"GL1902"},
            ),
            (
                {"spark_druid_olap_tpu/exec/streaming.py": """
                    import jax.numpy as jnp

                    def move(self, seg):
                        host = seg.column("v")
                        return jnp.asarray(host)
                """},
                {"GL1902"},
            ),
        ],
        "clean": [
            # the pipeline module is the sanctioned home of device_put
            {"spark_druid_olap_tpu/exec/pipeline.py": """
                import jax

                def pipelined_put(host, sharding=None):
                    return jax.device_put(host, sharding)
            """},
            # _put_device_col is the engine's sanctioned placement; other
            # code fetches THROUGH it, and jnp.asarray of computed device
            # values / staged constants stays legal
            {"spark_druid_olap_tpu/exec/engine.py": """
                import jax.numpy as jnp

                def _put_device_col(self, key, host, ds_name):
                    arr = jnp.asarray(host)
                    self._device_cache[key] = arr
                    return arr

                def vcols(self, fns, cols):
                    for name, fn in fns.items():
                        cols[name] = jnp.asarray(fn(cols))
                    return cols
            """},
            # np.asarray of a host column is host-side work, not an h2d
            # move; parallel/ keeps its own sharded-placement contract
            {"spark_druid_olap_tpu/exec/fallback.py": """
                import numpy as np

                def decode(self, seg):
                    return np.asarray(seg.valid)
            """,
             "spark_druid_olap_tpu/parallel/distributed.py": """
                import jax

                def shard(self, host, sharding):
                    return jax.device_put(host, sharding)
            """},
        ],
    },
    "storage-discipline": {
        "violating": [
            # GL2001: append path publishes without journaling — an
            # acked append a restart silently forgets
            (
                {"spark_druid_olap_tpu/ingest/delta.py": """
                    class IngestManager:
                        def append_rows(self, name, rows):
                            ds = self.catalog.get(name)
                            return self.catalog.put(ds)
                """},
                {"GL2001"},
            ),
            # GL2002: snapshot written straight to its final name — a
            # crash mid-write leaves a torn file the next boot loads
            (
                {"spark_druid_olap_tpu/storage.py": """
                    import json

                    def save_snapshot(snap, path):
                        with open(path, "w") as f:
                            json.dump(snap, f)
                """},
                {"GL2002"},
            ),
            # GL2003: WAL replay loop with no checkpoint — invisible to
            # the deadline budget AND the crash-injection matrix
            (
                {"spark_druid_olap_tpu/ingest/wal.py": """
                    class WriteAheadLog:
                        def replay(self, apply):
                            for rec in self.scan_wal():
                                apply(rec)
                """},
                {"GL2003"},
            ),
        ],
        "clean": [
            # journaled publish, atomic snapshot commit, checkpointed
            # replay loop — the real tier's shapes
            {"spark_druid_olap_tpu/ingest/delta.py": """
                class IngestManager:
                    def append_rows(self, name, rows):
                        cols = self._normalize(rows)
                        self._journal(name, cols)
                        ds = self.catalog.get(name)
                        return self.catalog.put(ds)
            """,
             "spark_druid_olap_tpu/storage.py": """
                import json
                import os

                from .resilience import checkpoint

                def save_snapshot(snap, path):
                    tmp = path + ".tmp"
                    with open(tmp, "w") as f:
                        json.dump(snap, f)
                    os.replace(tmp, path)

                def recover(wal, ingest):
                    for rec in wal.replay_after(-1):
                        checkpoint("storage.replay_batch")
                        ingest.replay_batch(rec)
            """},
            # the append-mode journal write is the sanctioned non-atomic
            # exception; the same write shapes OUTSIDE the storage tier
            # are other passes' business
            {"spark_druid_olap_tpu/ingest/wal.py": """
                import io

                import numpy as np

                class WriteAheadLog:
                    def _handle(self):
                        return open(self.path, "ab")

                def atomic_write_array(path, arr):
                    buf = io.BytesIO()
                    np.save(buf, arr)
                    atomic_write_bytes(path, buf.getvalue())
            """,
             "spark_druid_olap_tpu/exec/engine.py": """
                import json

                def dump_debug(doc, path):
                    with open(path, "w") as f:
                        json.dump(doc, f)
            """},
        ],
    },
    "dispatch-discipline": {
        "violating": [
            # GL2101: a dispatch span inside a host loop is the
            # per-segment round-trip the one-dispatch arena collapsed
            (
                {"spark_druid_olap_tpu/exec/custom_exec.py": """
                    from ..obs import SPAN_SEGMENT_DISPATCH, span

                    def scan_all(self, fn, batches):
                        out = []
                        for bi, batch in enumerate(batches):
                            with span(SPAN_SEGMENT_DISPATCH, batch=bi):
                                out.append(fn(batch))
                        return out
                """},
                {"GL2101"},
            ),
            # GL2101 also matches the runtime string span name, and the
            # serving tree is in scope too
            (
                {"spark_druid_olap_tpu/serve/drain.py": """
                    from ..obs import span

                    def drain(self, fn, queue):
                        while queue:
                            with span("sparse_dispatch"):
                                fn(queue.pop())
                """},
                {"GL2101"},
            ),
            # GL2102: jax.jit built per iteration retraces/recompiles
            # every pass and never hits the program cache
            (
                {"spark_druid_olap_tpu/exec/retrace.py": """
                    import jax

                    def per_segment(self, build, segs):
                        acc = []
                        for seg in segs:
                            fn = jax.jit(build(seg))
                            acc.append(fn(seg.cols))
                        return acc
                """},
                {"GL2102"},
            ),
        ],
        "clean": [
            # the engine's remainder loop and the arena's chunk loop are
            # the sanctioned dispatch-loop owners
            {"spark_druid_olap_tpu/exec/engine.py": """
                from ..obs import SPAN_SEGMENT_DISPATCH, span

                def _partials_for_query(self, q, ds, seg_fn, batches):
                    for bi, batch in enumerate(batches):
                        with span(SPAN_SEGMENT_DISPATCH, batch=bi):
                            seg_fn(batch)
            """,
             "spark_druid_olap_tpu/exec/arena.py": """
                from ..obs import SPAN_SEGMENT_DISPATCH, span

                def run_plan(engine, program, chunks):
                    for ci, (lo, hi) in enumerate(chunks):
                        with span(SPAN_SEGMENT_DISPATCH, chunk=ci):
                            program(lo, hi)
            """},
            # program built ONCE then called in the loop; non-dispatch
            # spans (h2d staging) in loops stay legal
            {"spark_druid_olap_tpu/exec/engine.py": """
                import jax

                from ..obs import SPAN_H2D, span

                def warm(self, build, batches):
                    fn = jax.jit(build())
                    out = []
                    for bi, batch in enumerate(batches):
                        with span(SPAN_H2D, batch=bi):
                            out.append(fn(batch))
                    return out
            """},
            # parallel/ keeps its own sharded-dispatch contract
            {"spark_druid_olap_tpu/parallel/distributed.py": """
                from ..obs import SPAN_SEGMENT_DISPATCH, span

                def merge(self, fn, shards):
                    for s in shards:
                        with span(SPAN_SEGMENT_DISPATCH):
                            fn(s)
            """},
        ],
    },
    "mesh-discipline": {
        "violating": [
            # GL2201: a string-literal collective axis bypasses the
            # single-declaration *_AXIS contract — it keeps "working"
            # after an axis-layout change while merging the wrong scope
            (
                {"spark_druid_olap_tpu/exec/custom_merge.py": """
                    from jax import lax

                    def merge(state):
                        return lax.psum(state, "data")
                """},
                {"GL2201"},
            ),
            # GL2202: sharded placement in parallel/ outside a
            # sanctioned owner bypasses residency keys, the h2d fault
            # site, link accounting, and the multi-process shim
            (
                {"spark_druid_olap_tpu/parallel/warm.py": """
                    import jax

                    def warm_column(host, sharding):
                        return jax.device_put(host, sharding)
                """},
                {"GL2202"},
            ),
            # GL2203: a dispatch span in a host loop on the SPMD path
            # is the per-shard round trip the sharded arena collapsed
            (
                {"spark_druid_olap_tpu/parallel/looper.py": """
                    from ..obs import SPAN_SEGMENT_DISPATCH, span

                    def merge_each(self, fn, shards):
                        for s in shards:
                            with span(SPAN_SEGMENT_DISPATCH):
                                fn(s)
                """},
                {"GL2203"},
            ),
        ],
        "clean": [
            # declared-constant axes, and placement inside the owners
            {"spark_druid_olap_tpu/parallel/mesh.py": """
                DATA_AXIS = "data"
            """,
             "spark_druid_olap_tpu/parallel/distributed.py": """
                import jax
                from jax import lax

                from .mesh import DATA_AXIS

                def _place_shards(self, host, sharding):
                    return jax.device_put(host, sharding)

                def merged(state):
                    return lax.psum(state, DATA_AXIS)
            """},
            # the chunked anytime loop is the sanctioned dispatch-loop
            # owner (one iteration per deadline checkpoint, not per
            # shard); bare default-device puts are out of scope here
            {"spark_druid_olap_tpu/parallel/spmd_arena.py": """
                import jax

                from ..obs import SPAN_SEGMENT_DISPATCH, span

                def _arena_spmd_deadline(self, chunk, steps):
                    for j in steps:
                        with span(SPAN_SEGMENT_DISPATCH, chunk=j):
                            chunk(j)

                def stage(host):
                    return jax.device_put(host)
            """},
        ],
    },
    "broker-discipline": {
        "violating": [
            # GL2301: replica states folded with no version reference
            # anywhere in the enclosing function — a cross-generation
            # merge with agreeing shapes is silently wrong
            (
                {"spark_druid_olap_tpu/cluster/gatherer.py": """
                    def fold(engine, q, ds, state, replies):
                        for r in replies:
                            state = engine.merge_groupby_states(
                                q, ds, state, r["state"]
                            )
                        return state
                """},
                {"GL2301"},
            ),
            # GL2302: a failover/retry loop issuing RPCs with no
            # resilience checkpoint — uninjectable and unbounded
            (
                {"spark_druid_olap_tpu/cluster/scatterer.py": """
                    import urllib.request

                    def walk_chain(chain, payload):
                        for node_url in chain:
                            try:
                                return urllib.request.urlopen(
                                    node_url, payload
                                )
                            except OSError:
                                continue
                """},
                {"GL2302"},
            ),
            # GL2303: routing on a breaker's raw _state races the
            # half-open probe bookkeeping under the breaker's lock
            (
                {"spark_druid_olap_tpu/cluster/router.py": """
                    def pick(nodes, breakers):
                        return [
                            n for n in nodes
                            if breakers[n]._state == "closed"
                        ]
                """},
                {"GL2303"},
            ),
            # GL2303 also fires on the distinctive fields through any
            # receiver, including self outside CircuitBreaker
            (
                {"spark_druid_olap_tpu/serve/probe.py": """
                    class Router:
                        def healthy(self, br):
                            return br._consecutive_failures == 0
                """},
                {"GL2303"},
            ),
        ],
        "clean": [
            # version-checked gather + checkpointed scatter loop +
            # public breaker accessors: the whole contract held
            {"spark_druid_olap_tpu/cluster/gatherer.py": """
                import urllib.request

                from ..resilience import checkpoint

                def fold(engine, q, ds, state, replies, expect_version):
                    for r in replies:
                        if r["version"] != expect_version:
                            continue
                        state = engine.merge_groupby_states(
                            q, ds, state, r["state"]
                        )
                    return state

                def walk_chain(chain, payload):
                    for node_url in chain:
                        checkpoint("cluster.scatter")
                        try:
                            return urllib.request.urlopen(node_url, payload)
                        except OSError:
                            continue

                def live(nodes, breakers):
                    return [n for n in nodes if breakers[n].state != "open"]
            """},
            # CircuitBreaker owns its fields; other classes own their
            # own self._state; external code reads the public surface
            {"spark_druid_olap_tpu/resilience.py": """
                import threading

                class CircuitBreaker:
                    def __init__(self):
                        self._lock = threading.Lock()
                        self._state = "closed"

                    @property
                    def state(self):
                        with self._lock:
                            return self._state
            """,
             "spark_druid_olap_tpu/serve/drainer.py": """
                class Drainer:
                    def __init__(self):
                        self._state = "idle"

                    def snapshot(self, breaker):
                        return {
                            "drain": self._state,
                            "breaker": breaker.to_dict(),
                        }
            """},
        ],
    },
    "fold-determinism": {
        "violating": [
            # GL2401: folding straight out of as_completed — completion
            # order is scheduler-dependent, so a non-commutative merge
            # gives run-to-run different results
            (
                {"spark_druid_olap_tpu/cluster/gather.py": """
                    from concurrent.futures import as_completed

                    def gather(engine, q, ds, futs):
                        state = None
                        for fut in as_completed(futs):
                            state = engine.merge_groupby_states(
                                q, ds, state, fut.result()
                            )
                        return state
                """},
                {"GL2401"},
            ),
            # GL2401 via os.listdir + GL2402: the order-tainted list is
            # itself handed to the sink as an argument
            (
                {"spark_druid_olap_tpu/exec/segloop.py": """
                    import os

                    def fold_dir(engine, q, ds, root):
                        state = None
                        for name in os.listdir(root):
                            state = engine.merge_sketch_states(
                                q, ds, state, name
                            )
                        return state

                    def fold_batch(engine, q, ds, futs):
                        from concurrent.futures import as_completed
                        rs = [f.result() for f in as_completed(futs)]
                        return engine.merge_groupby_states(q, ds, None, rs)
                """},
                {"GL2401", "GL2402"},
            ),
            # GL2403: the unordered gather crosses a helper boundary —
            # the fold lives in a callee whose summary says
            # "param reaches sink"
            (
                {"spark_druid_olap_tpu/cluster/deep.py": """
                    from concurrent.futures import as_completed

                    def _fold(engine, q, ds, items):
                        state = None
                        for r in items:
                            state = engine.merge_timeseries_states(
                                q, ds, state, r
                            )
                        return state

                    def gather(engine, q, ds, futs):
                        rs = [f.result() for f in as_completed(futs)]
                        return _fold(engine, q, ds, rs)
                """},
                {"GL2403"},
            ),
        ],
        "clean": [
            # the broker idiom this pass enforces: collect, sort by a
            # stable key, then fold — sorted() sanitizes the order taint
            {"spark_druid_olap_tpu/cluster/gather.py": """
                from concurrent.futures import as_completed

                def gather(engine, q, ds, futs):
                    results = []
                    for fut in as_completed(futs):
                        results.append(fut.result())
                    state = None
                    for r in sorted(results, key=lambda t: t[0]):
                        state = engine.merge_groupby_states(
                            q, ds, state, r
                        )
                    return state
            """},
            # dict iteration is insertion-ordered in CPython — folding
            # grouped states out of a dict is deterministic, and a
            # .sort() in place sanitizes like sorted()
            {"spark_druid_olap_tpu/exec/groupfold.py": """
                import os

                def fold_groups(engine, q, ds, by_key):
                    state = None
                    for k, v in by_key.items():
                        state = engine.merge_groupby_states(
                            q, ds, state, v
                        )
                    return state

                def fold_dir(engine, q, ds, root):
                    names = list(os.listdir(root))
                    names.sort()
                    state = None
                    for name in names:
                        state = engine.merge_sketch_states(
                            q, ds, state, name
                        )
                    return state
            """},
        ],
    },
    "shared-state-races": {
        "violating": [
            # GL2501 off-lock read-modify-write + GL2502 off-lock
            # container mutation: _lock owns both fields (majority of
            # writes are guarded), so the unguarded accesses race
            (
                {"spark_druid_olap_tpu/serve/registry.py": """
                    import threading

                    class Registry:
                        def __init__(self):
                            self._lock = threading.Lock()
                            self._entries = {}
                            self.version = 0

                        def put(self, k, v):
                            with self._lock:
                                self._entries[k] = v
                                self.version += 1

                        def drop(self, k):
                            with self._lock:
                                self._entries.pop(k, None)
                                self.version += 1

                        def bump_unsafely(self):
                            self.version = self.version + 1

                        def clear_unsafely(self):
                            self._entries.clear()
                """},
                {"GL2501", "GL2502"},
            ),
            # GL2503 off-lock write through an external typed reference
            # (module-level singleton) + GL2504 off-lock iteration in
            # thread-reachable code (Thread target calls the method)
            (
                {"spark_druid_olap_tpu/serve/registry.py": """
                    import threading

                    class Registry:
                        def __init__(self):
                            self._lock = threading.Lock()
                            self._entries = {}
                            self.version = 0

                        def put(self, k, v):
                            with self._lock:
                                self._entries[k] = v
                                self.version += 1

                        def drop(self, k):
                            with self._lock:
                                self._entries.pop(k, None)
                                self.version += 1

                        def keys_unsafely(self):
                            return [k for k in self._entries]


                    REGISTRY = Registry()


                    def reset_version():
                        REGISTRY.version = 0


                    def worker():
                        REGISTRY.put("a", 1)
                        for k in REGISTRY.keys_unsafely():
                            pass


                    def spawn():
                        t = threading.Thread(target=worker)
                        t.start()
                        return t
                """},
                {"GL2503", "GL2504"},
            ),
        ],
        "clean": [
            # the contract held: every touch of the owned fields is
            # under the owning lock, snapshots copy before returning
            {"spark_druid_olap_tpu/serve/registry.py": """
                import threading

                class Registry:
                    def __init__(self):
                        self._lock = threading.Lock()
                        self._entries = {}
                        self.version = 0

                    def put(self, k, v):
                        with self._lock:
                            self._entries[k] = v
                            self.version += 1

                    def drop(self, k):
                        with self._lock:
                            self._entries.pop(k, None)
                            self.version += 1

                    def snapshot(self):
                        with self._lock:
                            return dict(self._entries)
            """},
            # no inferable owner: the field is mostly written unguarded
            # (single-threaded builder), so majority inference leaves it
            # unowned rather than guessing — and __init__ writes never
            # count against ownership
            {"spark_druid_olap_tpu/exec/builder.py": """
                import threading

                class PlanBuilder:
                    def __init__(self):
                        self._lock = threading.Lock()
                        self._steps = []
                        self._flushed = 0

                    def add(self, s):
                        self._steps.append(s)

                    def reset(self):
                        self._steps = []

                    def note(self):
                        self._flushed = self._flushed + 1

                    def rare_locked_use(self):
                        with self._lock:
                            self._steps = list(self._steps)
            """},
        ],
    },
    "sanitizer-discipline": {
        "violating": [
            # GL2601: probe inside a @jit-traced body — the witness is
            # trace-time constant-folded and enforces nothing
            (
                {"spark_druid_olap_tpu/exec/traced.py": """
                    import jax

                    from tools import graftsan

                    @jax.jit
                    def fold_kernel_host(x):
                        graftsan.probe_count()
                        return x + 1
                """},
                {"GL2601"},
            ),
            # GL2601 via kernel-name suffix (pallas kernels have no
            # decorator)
            (
                {"spark_druid_olap_tpu/exec/kernels.py": """
                    from tools import graftsan

                    def groupby_kernel(refs):
                        graftsan.probe_count()
                        return refs
                """},
                {"GL2601"},
            ),
            # GL2602: bare probe in product code, no arm guard — every
            # unsanitized process pays for it
            (
                {"spark_druid_olap_tpu/serve/probe.py": """
                    from tools import graftsan

                    def handle(req):
                        graftsan.probe_count()
                        return req
                """},
                {"GL2602"},
            ),
            (
                {"spark_druid_olap_tpu/exec/hooky.py": """
                    _sched_hook = None

                    def checkpoint(site):
                        _sched_hook(site)
                """},
                {"GL2602"},
            ),
        ],
        "clean": [
            # the resilience null-hook idiom: one global None check
            {"spark_druid_olap_tpu/exec/hooky.py": """
                _sched_hook = None

                def checkpoint(site):
                    if _sched_hook is not None:
                        _sched_hook(site)
            """},
            # explicit SDOL_SANITIZE arm check, env-var and helper forms
            {"spark_druid_olap_tpu/serve/probe.py": """
                import os

                from tools import graftsan

                def handle(req):
                    if os.environ.get("SDOL_SANITIZE"):
                        graftsan.probe_count()
                    if graftsan.enabled():
                        graftsan.probe_count()
                    return req
            """},
        ],
    },
    "trace-propagation": {
        "violating": [
            # GL2701: scatter RPC built with no trace-header propagation
            # anywhere in the enclosing function
            (
                {"spark_druid_olap_tpu/cluster/sender.py": """
                    import urllib.request

                    def rpc(url, payload):
                        req = urllib.request.Request(
                            url + "/druid/v2/cluster/partial",
                            data=payload,
                            method="POST",
                        )
                        return urllib.request.urlopen(req)
                """},
                {"GL2701"},
            ),
            # GL2702: graft-point span opened under an ad-hoc name the
            # registry does not know
            (
                {
                    "spark_druid_olap_tpu/obs/trace.py": """
                        SPAN_CLUSTER_RPC = "cluster_rpc"
                    """,
                    "spark_druid_olap_tpu/cluster/graft.py": """
                        from ..obs.trace import span_in

                        def attempt(trace, parent, node):
                            with span_in(trace, parent, "rpc-" + node):
                                return node
                    """,
                },
                {"GL2702"},
            ),
            # GL2703: federation fan-out loop with no checkpoint — one
            # hung node stalls the whole merged scrape
            (
                {"spark_druid_olap_tpu/cluster/fed.py": """
                    import urllib.request

                    def scrape_all(nodes):
                        out = {}
                        for nid, url in sorted(nodes.items()):
                            with urllib.request.urlopen(url) as r:
                                out[nid] = r.read()
                        return out
                """},
                {"GL2703"},
            ),
        ],
        "clean": [
            # GL2701 clean: headers built by wire.trace_headers and
            # merged through
            {"spark_druid_olap_tpu/cluster/sender.py": """
                import urllib.request

                def trace_headers(qid, span_id):
                    return {"X-Druid-Query-Id": qid}

                def rpc(url, payload, qid):
                    req = urllib.request.Request(
                        url + "/druid/v2/cluster/partial",
                        data=payload,
                        headers=trace_headers(qid, ""),
                        method="POST",
                    )
                    return urllib.request.urlopen(req)
            """},
            # GL2702 clean: graft point named by a registered SPAN_*
            # constant resolved through the import
            {
                "spark_druid_olap_tpu/obs/trace.py": """
                    SPAN_CLUSTER_RPC = "cluster_rpc"
                """,
                "spark_druid_olap_tpu/cluster/graft.py": """
                    from ..obs.trace import SPAN_CLUSTER_RPC, span_in

                    def attempt(trace, parent, node):
                        with span_in(trace, parent, SPAN_CLUSTER_RPC):
                            return node
                """,
            },
            # GL2703 clean: per-node checkpoint inside the fetch loop
            {"spark_druid_olap_tpu/cluster/fed.py": """
                import urllib.request

                from ..resilience import checkpoint

                def scrape_all(nodes):
                    out = {}
                    for nid, url in sorted(nodes.items()):
                        checkpoint("cluster.federate")
                        with urllib.request.urlopen(url) as r:
                            out[nid] = r.read()
                    return out
            """},
            # GL2703 clean: the fetch call sits in the ITER expression —
            # it runs once before the loop, the body only decodes, so
            # the per-node bound belongs inside the fan-out helper (the
            # real federation.scrape_nodes_json shape)
            {"spark_druid_olap_tpu/cluster/fed.py": """
                import json

                from ..resilience import checkpoint

                def fetch_one(url):
                    checkpoint("cluster.federate")
                    return "{}"

                def scrape_all(nodes):
                    return {
                        nid: fetch_one(url)
                        for nid, url in sorted(nodes.items())
                    }

                def scrape_all_json(nodes):
                    docs = {}
                    for nid, text in scrape_all(nodes).items():
                        docs[nid] = json.loads(text)
                    return docs
            """},
        ],
    },
    "durability-protocol": {
        "violating": [
            (
                # publish hoisted above the journal+fsync pair: the
                # automaton's later:journal evidence makes this a true
                # reorder, not an ephemeral (never-journaled) path
                {"spark_druid_olap_tpu/ingest/wal.py": """
                    from ..resilience import checkpoint

                    class WriteAheadLog:
                        def append(self, ds, rows):
                            self.catalog.put(ds)
                            checkpoint("wal.journal_write")
                            checkpoint("wal.post_fsync_pre_publish")
                            return True
                """},
                {"GL2801"},
            ),
            (
                # GC before the snapshot-rename commit point
                {"spark_druid_olap_tpu/storage.py": """
                    import os

                    from .resilience import checkpoint

                    class DurableStorage:
                        def flush_locked(self, name, ds):
                            os.remove(self._old_snapshot(name))
                            checkpoint("persist.snapshot_rename")
                            os.replace(self._tmp(name), self._snap(name))
                """},
                {"GL2802"},
            ),
            (
                # exception escapes in the post-fsync pre-publish window
                # of a function with NO whole-or-absent exemption: an
                # acked-but-unpublished row would surface on recovery
                {"spark_druid_olap_tpu/wal2.py": """
                    from .resilience import checkpoint

                    class WriteAheadLog:
                        def append(self, ds, rows):
                            checkpoint("wal.journal_write")
                            checkpoint("wal.post_fsync_pre_publish")
                            self.catalog.put(ds)
                            return True
                """},
                {"GL2803"},
            ),
        ],
        "clean": [
            # the real append shape at its REAL canonical name: the
            # publish may still raise post-fsync, but the whole_or_absent
            # table discharges that to the recovery scan + raise matrix
            {"spark_druid_olap_tpu/ingest/delta.py": """
                from ..resilience import checkpoint

                class IngestManager:
                    def append_rows(self, name, rows):
                        checkpoint("wal.journal_write")
                        checkpoint("wal.post_fsync_pre_publish")
                        self.catalog.put(self._fold(name, rows))
                        return {"rows": len(rows)}
            """},
            # rename commits BEFORE the GC/truncate: the flush exemplar
            {"spark_druid_olap_tpu/storage.py": """
                import os

                from .resilience import checkpoint

                class DurableStorage:
                    def flush_locked(self, name, ds):
                        checkpoint("persist.snapshot_rename")
                        os.replace(self._tmp(name), self._snap(name))
                        checkpoint("compact.retire")
                        os.remove(self._old_snapshot(name))
                        self.wal(name).truncate_through(ds)
            """},
            # a raise in the durable window REPAIRED by a catch-all
            # handler: the exception never escapes, so no GL2803
            {"spark_druid_olap_tpu/wal3.py": """
                from .resilience import checkpoint

                class WriteAheadLog:
                    def append(self, ds, rows):
                        checkpoint("wal.journal_write")
                        checkpoint("wal.post_fsync_pre_publish")
                        try:
                            self.catalog.put(ds)
                        except Exception:
                            self._mark_unpublished(ds)
                            return False
                        return True
            """},
            # an ephemeral path that never journals may publish freely:
            # later:journal keeps the start-state error evidence-gated
            {"spark_druid_olap_tpu/ingest/delta.py": """
                from ..resilience import checkpoint

                class IngestManager:
                    def append_rows(self, name, rows):
                        if self.storage is not None:
                            checkpoint("wal.journal_write")
                            checkpoint("wal.post_fsync_pre_publish")
                        self.catalog.put(self._fold(name, rows))
                        return {"rows": len(rows)}
            """},
        ],
    },
    "cleanup-safety": {
        "violating": [
            (
                # the may-raise checkpoint sits between acquire and
                # release with no finally: the slot leaks on that edge
                {"spark_druid_olap_tpu/serve/lanes.py": """
                    from ..resilience import checkpoint

                    class LaneGate:
                        def run(self, res, q):
                            if not res.admission.acquire():
                                return None
                            checkpoint("serve.lane_execute")
                            out = self._execute(q)
                            res.admission.release()
                            return out
                """},
                {"GL2901"},
            ),
            (
                # exception between two owned-field writes inside ONE
                # lock region: the unwind publishes the torn prefix
                {"spark_druid_olap_tpu/state.py": """
                    import threading

                    from .resilience import checkpoint

                    class BrokerState:
                        def __init__(self):
                            self._lock = threading.Lock()
                            self._epoch = 0
                            self._assignment = {}

                        def apply(self, epoch, assignment):
                            with self._lock:
                                self._epoch = epoch
                                checkpoint("cluster.apply")
                                self._assignment = dict(assignment)
                """},
                {"GL2902"},
            ),
            (
                # the finally's release path re-acquires its own
                # resource: cleanup can fail exactly when it must not
                {"spark_druid_olap_tpu/serve/spans.py": """
                    class SpanPool:
                        def run(self, res, q):
                            res.spans.acquire()
                            try:
                                return self._execute(q)
                            finally:
                                res.spans.acquire()
                                res.spans.release()
                """},
                {"GL2903"},
            ),
        ],
        "clean": [
            # nullness-guarded acquire/release: the effect layer's
            # truth+fact tracking balances `res is None or ...acquire()`
            # against the guarded finally release
            {"spark_druid_olap_tpu/serve/lanes.py": """
                from ..resilience import checkpoint

                class LaneGate:
                    def run(self, res, q):
                        admitted = res is None or res.admission.acquire()
                        if not admitted:
                            return None
                        try:
                            checkpoint("serve.lane_execute")
                            return self._execute(q)
                        finally:
                            if res is not None:
                                res.admission.release()
            """},
            # plain try/finally release: every raise edge releases
            {"spark_druid_olap_tpu/serve/lanes.py": """
                from ..resilience import checkpoint

                class LaneGate:
                    def run(self, res, q):
                        if not res.admission.acquire():
                            return None
                        try:
                            checkpoint("serve.lane_execute")
                            return self._execute(q)
                        finally:
                            res.admission.release()
            """},
            # owned writes in SEPARATE lock regions: each region is
            # individually consistent, crossing them never flags
            {"spark_druid_olap_tpu/state.py": """
                import threading

                from .resilience import checkpoint

                class BrokerState:
                    def __init__(self):
                        self._lock = threading.Lock()
                        self._epoch = 0
                        self._assignment = {}

                    def apply(self, epoch, assignment):
                        with self._lock:
                            self._epoch = epoch
                        checkpoint("cluster.apply")
                        with self._lock:
                            self._assignment = dict(assignment)
            """},
        ],
    },
}


def test_matrix_covers_every_pass_with_minimum_fixtures():
    names = {cls.name for cls in ALL_PASSES}
    assert set(_MATRIX) == names
    for name, cases in _MATRIX.items():
        assert len(cases["violating"]) >= 2, name
        assert len(cases["clean"]) >= 2, name


@pytest.mark.parametrize("pass_name", sorted(_MATRIX))
def test_violating_fixtures_are_flagged(pass_name, tmp_path):
    for i, (files, want_codes) in enumerate(_MATRIX[pass_name]["violating"]):
        sub = tmp_path / f"v{i}"
        res = _run_on(sub, files, passes=[pass_name])
        got_codes = {f.code for f in res.new}
        assert want_codes <= got_codes, (
            f"{pass_name} fixture {i}: wanted {want_codes}, got "
            f"{[f.render() for f in res.new]}"
        )
        assert all(f.pass_name == pass_name for f in res.new)


@pytest.mark.parametrize("pass_name", sorted(_MATRIX))
def test_clean_fixtures_pass(pass_name, tmp_path):
    for i, files in enumerate(_MATRIX[pass_name]["clean"]):
        sub = tmp_path / f"c{i}"
        res = _run_on(sub, files, passes=[pass_name])
        assert res.new == [], (
            f"{pass_name} clean fixture {i} flagged: "
            f"{[f.render() for f in res.new]}"
        )


def test_framework_pragma_suppresses_any_pass(tmp_path):
    res = _run_on(
        tmp_path,
        {"pkg/p.py": """
            import threading

            class CircuitBreaker:
                def __init__(self):
                    self._lock = threading.Lock()
                    self._state = "closed"

                def trip(self):
                    # graftlint: disable=lock-discipline -- single-threaded test helper
                    self._state = "open"
        """},
        passes=["lock-discipline"],
    )
    assert res.new == []


# ---------------------------------------------------------------------------
# Repo gate (THE lint gate) + baseline meta-tests
# ---------------------------------------------------------------------------


def test_repo_tree_is_lint_clean():
    res = run_lint(_ROOT, _TARGETS)
    assert set(res.pass_names) == {cls.name for cls in ALL_PASSES}
    assert res.new == [], "\n".join(f.render() for f in res.new)


def test_baseline_entries_all_still_exist():
    """Stale baseline entries (the finding was fixed but the entry kept)
    fail: the baseline may only shrink on its own."""
    res = run_lint(_ROOT, _TARGETS)
    assert res.stale == [], "\n".join(
        f"stale: {e.path} [{e.pass_name}/{e.code}] {e.snippet!r}"
        for e in res.stale
    )
    # and every grandfathered finding carries a real justification
    for f, e in res.baselined:
        assert e.reason.strip(), f.render()


def test_contract_export_is_current():
    """`graftsan_contracts.json` mirrors the baseline workflow: the
    committed file regenerated from the tree must be an exact no-op, so
    the runtime sanitizer can never enforce a stale table."""
    from tools.graftlint.contracts import (
        CONTRACTS_NAME,
        build_contract_doc,
        load_contracts,
    )

    committed = load_contracts(os.path.join(_ROOT, CONTRACTS_NAME))
    assert build_contract_doc(_ROOT) == committed, (
        "stale contract export: run "
        "`python -m tools.graftlint --export-contracts`"
    )


def test_cli_export_contracts_writes_table(tmp_path):
    _write_tree(tmp_path, {
        "spark_druid_olap_tpu/state.py": """
            import threading

            class Store:
                def __init__(self):
                    self._lock = threading.Lock()
                    self._aux = threading.Lock()
                    self.count = 0
                    self.tag = ""

                def locked_bump(self):
                    with self._lock:
                        self.count += 1

                def locked_bump2(self):
                    with self._lock:
                        self.count += 1

                def tag_it(self):
                    with self._aux:
                        # graftlint: owner=_aux
                        self.tag = "x"
        """,
    })
    out = _cli(
        ["spark_druid_olap_tpu", "--export-contracts"], cwd=str(tmp_path)
    )
    assert out.returncode == 0, out.stderr
    assert "contracts exported" in out.stdout
    with open(tmp_path / "graftsan_contracts.json") as f:
        doc = json.load(f)
    rows = {(r["class"], r["field"]): r for r in doc["lock_ownership"]}
    assert rows[("Store", "count")]["lock"] == "_lock"
    assert rows[("Store", "count")]["source"] == "majority"
    # the owner pin reaches the export, marked as human-sourced
    assert rows[("Store", "tag")]["lock"] == "_aux"
    assert rows[("Store", "tag")]["source"] == "annotation"
    assert doc["lock_attrs"]["spark_druid_olap_tpu.state.Store"] == [
        "_aux", "_lock",
    ]
    assert any(s["kind"] == "canonical-fold" for s in doc["fold_sinks"])
    # the GL28xx protocol machines ride along verbatim (ISSUE 20):
    # JSON-shaped automata + site->effect table + exemptions + probes
    assert [a["name"] for a in doc["protocol_automata"]] == [
        "durable-publish", "snapshot-commit",
    ]
    assert doc["effect_sites"]["wal.journal_write"] == "journal"
    assert doc["effect_sites"]["persist.snapshot_rename"] == "rename"
    assert doc["whole_or_absent"]
    assert {p["effect"] for p in doc["protocol_probes"]} == {
        "publish", "acquire", "release",
    }
    # deterministic: a second export is byte-identical
    first = (tmp_path / "graftsan_contracts.json").read_bytes()
    out = _cli(
        ["spark_druid_olap_tpu", "--export-contracts"], cwd=str(tmp_path)
    )
    assert out.returncode == 0
    assert (tmp_path / "graftsan_contracts.json").read_bytes() == first


def test_baseline_without_reason_is_rejected(tmp_path):
    (tmp_path / "m.py").write_text("x = 1\n")
    bl = tmp_path / "graftlint_baseline.json"
    bl.write_text(json.dumps({
        "entries": [{
            "pass": "jit-cache", "code": "GL101", "path": "m.py",
            "snippet": "x = 1", "reason": "  ",
        }],
    }))
    with pytest.raises(LintConfigError):
        run_lint(str(tmp_path), ["m.py"], baseline_path=str(bl))


def test_stale_baseline_entry_detected(tmp_path):
    (tmp_path / "m.py").write_text("x = 1\n")
    bl = tmp_path / "graftlint_baseline.json"
    bl.write_text(json.dumps({
        "entries": [{
            "pass": "jit-cache", "code": "GL101", "path": "m.py",
            "snippet": "f = jax.jit(lambda v: v)", "reason": "was fixed",
        }],
    }))
    res = run_lint(str(tmp_path), ["m.py"], baseline_path=str(bl))
    assert len(res.stale) == 1
    assert not res.ok


def test_baselined_finding_does_not_fail(tmp_path):
    (tmp_path / "pkg").mkdir()
    (tmp_path / "pkg" / "m.py").write_text(
        "import jax\n\n"
        "def handler(x):\n"
        "    f = jax.jit(lambda v: v + 1)\n"
        "    return f(x)\n"
    )
    bl = tmp_path / "graftlint_baseline.json"
    bl.write_text(json.dumps({
        "entries": [{
            "pass": "jit-cache", "code": "GL101", "path": "pkg/m.py",
            "snippet": "f = jax.jit(lambda v: v + 1)",
            "reason": "fixture: deliberately grandfathered",
        }],
    }))
    res = run_lint(str(tmp_path), ["pkg"], baseline_path=str(bl))
    assert res.new == [] and res.stale == [] and len(res.baselined) == 1
    assert res.ok


# ---------------------------------------------------------------------------
# CLI contract
# ---------------------------------------------------------------------------


def test_cli_clean_on_repo_tree():
    out = _cli(_TARGETS, cwd=_ROOT)
    assert out.returncode == 0, out.stdout + out.stderr


def test_cli_flags_introduced_violation(tmp_path):
    pkg = tmp_path / "pkg"
    pkg.mkdir()
    (pkg / "bad.py").write_text(
        "import jax\n\njax.config.update(\"jax_enable_x64\", True)\n"
    )
    out = _cli(["pkg"], cwd=str(tmp_path))
    assert out.returncode == 1, out.stdout + out.stderr
    assert "GL402" in out.stdout


def test_cli_json_and_pass_filter(tmp_path):
    pkg = tmp_path / "pkg"
    pkg.mkdir()
    (pkg / "bad.py").write_text(
        "import jax\n\njax.config.update(\"jax_enable_x64\", True)\n"
        "\n\ndef f():\n    g = jax.jit(lambda v: v)\n    return g\n"
    )
    out = _cli(["--json", "pkg"], cwd=str(tmp_path))
    doc = json.loads(out.stdout)
    codes = {f["code"] for f in doc["findings"]}
    assert {"GL402", "GL101"} <= codes
    # --pass scopes to one pass only
    out = _cli(["--json", "--pass", "compat-import", "pkg"], cwd=str(tmp_path))
    doc = json.loads(out.stdout)
    assert {f["code"] for f in doc["findings"]} == {"GL402"}
    assert doc["passes"] == ["compat-import"]
    # unknown pass name is a config error (exit 2)
    out = _cli(["--pass", "nope", "pkg"], cwd=str(tmp_path))
    assert out.returncode == 2


def test_scoped_runs_do_not_report_out_of_scope_entries_stale():
    """A --pass or single-file run must not claim baseline entries from
    other passes/files are stale (they are out of scope, not fixed)."""
    res = run_lint(
        _ROOT, ["spark_druid_olap_tpu/server.py"],
        pass_names=["error-discipline"],
    )
    assert res.stale == []
    assert res.ok
    # the skipped entries are reported as out-of-scope, not dropped
    assert len(res.out_of_scope_entries) == len(load_baseline(
        os.path.join(_ROOT, "graftlint_baseline.json")
    ))
    out = _cli(["spark_druid_olap_tpu/server.py"], cwd=_ROOT)
    assert out.returncode == 0, out.stdout + out.stderr


def test_scoped_update_baseline_preserves_other_scopes(tmp_path):
    """--update-baseline under --pass (or a path subset) must carry
    out-of-scope entries through untouched, not delete them."""
    pkg = tmp_path / "pkg"
    pkg.mkdir()
    (pkg / "a.py").write_text(
        "import jax\n\njax.config.update(\"jax_enable_x64\", True)\n"
    )
    (pkg / "b.py").write_text(
        "import jax\n\n"
        "def handler(x):\n"
        "    f = jax.jit(lambda v: v + 1)\n"
        "    return f(x)\n"
    )
    # grandfather everything, then re-update scoped to one pass
    assert _cli(["--update-baseline", "pkg"], cwd=str(tmp_path)).returncode == 0
    before = load_baseline(str(tmp_path / "graftlint_baseline.json"))
    assert {e.pass_name for e in before} == {"compat-import", "jit-cache"}
    out = _cli(
        ["--update-baseline", "--pass", "jit-cache", "pkg"],
        cwd=str(tmp_path),
    )
    assert out.returncode == 0, out.stdout + out.stderr
    after = load_baseline(str(tmp_path / "graftlint_baseline.json"))
    assert {e.pass_name for e in after} == {"compat-import", "jit-cache"}
    # and a scoped update over a file subset keeps the other file's entry
    out = _cli(
        ["--update-baseline", "pkg/a.py"], cwd=str(tmp_path),
    )
    assert out.returncode == 0, out.stdout + out.stderr
    after = load_baseline(str(tmp_path / "graftlint_baseline.json"))
    assert {e.pass_name for e in after} == {"compat-import", "jit-cache"}
    # the full gate still passes afterwards
    assert _cli(["pkg"], cwd=str(tmp_path)).returncode == 0


def test_malformed_pragma_is_gl002(tmp_path):
    """A disable pragma with no pass list used to silently disable
    nothing; it is now an explicit core finding.  (The fixture source is
    assembled by concatenation so THIS file's repo-gate scan does not
    see a malformed pragma of its own.)"""
    src = (
        "# graftlint: " + "disable\n"
        "x = 1\n"
        "\n"
        "# graftlint: " + "disable= -- I promise this is fine\n"
        "y = 2\n"
    )
    res = _run_on(tmp_path, {"pkg/p.py": src})
    gl002 = [f for f in res.new if f.code == "GL002"]
    assert len(gl002) == 2, [f.render() for f in res.new]
    assert all(f.pass_name == "core" for f in gl002)


def test_wellformed_pragma_is_not_gl002(tmp_path):
    res = _run_on(
        tmp_path,
        {"pkg/p.py": """
            # graftlint: disable=jit-cache -- measured harness
            x = 1

            # prose mentioning that a check was disabled earlier
            y = 2
        """},
    )
    assert [f for f in res.new if f.code == "GL002"] == []


def test_format_github_matches_json(tmp_path):
    """--format github emits one ::error annotation per json finding,
    with matching file/line/code."""
    pkg = tmp_path / "pkg"
    pkg.mkdir()
    (pkg / "bad.py").write_text(
        "import jax\n\njax.config.update(\"jax_enable_x64\", True)\n"
        "\n\ndef f():\n    g = jax.jit(lambda v: v)\n    return g\n"
    )
    jout = _cli(["--format", "json", "pkg"], cwd=str(tmp_path))
    doc = json.loads(jout.stdout)
    want = {
        (f["path"], f["line"], f["pass_name"], f["code"])
        for f in doc["findings"]
    }
    gout = _cli(["--format", "github", "pkg"], cwd=str(tmp_path))
    assert gout.returncode == jout.returncode == 1
    got = set()
    for line in gout.stdout.splitlines():
        assert line.startswith("::error "), line
        fields = dict(
            kv.split("=", 1)
            for kv in line[len("::error "):].split("::", 1)[0].split(",")
        )
        pass_name, code = fields["title"].split("/")
        got.add((fields["file"], int(fields["line"]), pass_name, code))
    assert got == want and want


def test_update_baseline_preserves_reason_for_unchanged_identity(tmp_path):
    """An --update-baseline re-run must keep the justification of an
    entry whose finding still exists, verbatim."""
    pkg = tmp_path / "pkg"
    pkg.mkdir()
    (pkg / "bad.py").write_text(
        "import jax\n\njax.config.update(\"jax_enable_x64\", True)\n"
    )
    assert _cli(["--update-baseline", "pkg"], cwd=str(tmp_path)).returncode == 0
    bl = tmp_path / "graftlint_baseline.json"
    doc = json.loads(bl.read_text())
    doc["entries"][0]["reason"] = "deliberate: x64 harness"
    bl.write_text(json.dumps(doc))
    assert _cli(["--update-baseline", "pkg"], cwd=str(tmp_path)).returncode == 0
    entries = load_baseline(str(bl))
    assert [e.reason for e in entries] == ["deliberate: x64 harness"]


def test_update_baseline_preserves_reason_across_snippet_edit(tmp_path):
    """Editing the flagged line changes the finding's snippet identity;
    the (pass, code, path) fallback must carry the justification over
    instead of demanding re-entry."""
    pkg = tmp_path / "pkg"
    pkg.mkdir()
    (pkg / "bad.py").write_text(
        "import jax\n\njax.config.update(\"jax_enable_x64\", True)\n"
    )
    assert _cli(["--update-baseline", "pkg"], cwd=str(tmp_path)).returncode == 0
    bl = tmp_path / "graftlint_baseline.json"
    doc = json.loads(bl.read_text())
    doc["entries"][0]["reason"] = "deliberate: x64 harness"
    bl.write_text(json.dumps(doc))
    # reformat the flagged line: same violation, new snippet identity
    (pkg / "bad.py").write_text(
        "import jax\n\njax.config.update(\"jax_enable_x64\", bool(1))\n"
    )
    assert _cli(["--update-baseline", "pkg"], cwd=str(tmp_path)).returncode == 0
    entries = load_baseline(str(bl))
    assert len(entries) == 1
    assert entries[0].snippet == 'jax.config.update("jax_enable_x64", bool(1))'
    assert entries[0].reason == "deliberate: x64 harness"
    assert _cli(["pkg"], cwd=str(tmp_path)).returncode == 0


def test_update_baseline_new_finding_gets_placeholder_not_copied_reason(
    tmp_path,
):
    """A genuinely NEW violation with the same (pass, code, path) as a
    still-live justified entry must get the placeholder — it must not
    silently inherit the reviewed justification."""
    pkg = tmp_path / "pkg"
    pkg.mkdir()
    (pkg / "bad.py").write_text(
        "import jax\n\njax.config.update(\"jax_enable_x64\", True)\n"
    )
    assert _cli(["--update-baseline", "pkg"], cwd=str(tmp_path)).returncode == 0
    bl = tmp_path / "graftlint_baseline.json"
    doc = json.loads(bl.read_text())
    doc["entries"][0]["reason"] = "deliberate: x64 harness"
    bl.write_text(json.dumps(doc))
    # a SECOND, unrelated violation in the same file
    (pkg / "bad.py").write_text(
        "import jax\n\njax.config.update(\"jax_enable_x64\", True)\n"
        "jax.config.update(\"jax_enable_x64\", False)\n"
    )
    assert _cli(["--update-baseline", "pkg"], cwd=str(tmp_path)).returncode == 0
    reasons = {e.snippet: e.reason for e in load_baseline(str(bl))}
    assert reasons[
        'jax.config.update("jax_enable_x64", True)'
    ] == "deliberate: x64 harness"
    assert "justify before merge" in reasons[
        'jax.config.update("jax_enable_x64", False)'
    ]


def test_changed_mode_lints_only_diff_from_merge_base(tmp_path):
    """--changed scopes the run to files differing from
    merge-base(HEAD, BASE) plus untracked files."""
    pkg = tmp_path / "pkg"
    pkg.mkdir()
    (pkg / "clean.py").write_text("x = 1\n")
    (pkg / "stale_bad.py").write_text(
        "import jax\n\njax.config.update(\"jax_enable_x64\", True)\n"
    )
    assert _git(tmp_path, "init", "-q").returncode == 0
    _git(tmp_path, "branch", "-m", "main")
    _git(tmp_path, "config", "user.email", "t@example.com")
    _git(tmp_path, "config", "user.name", "t")
    _git(tmp_path, "add", "-A")
    assert _git(tmp_path, "commit", "-qm", "seed").returncode == 0
    # nothing differs from merge-base: zero files scanned, exit 0 — the
    # COMMITTED violation is out of scope (the full gate owns it)
    out = _cli(["--format", "json", "--changed"], cwd=str(tmp_path))
    doc = json.loads(out.stdout)
    assert out.returncode == 0 and doc["files_scanned"] == 0
    # an untracked violating file is in scope
    (pkg / "new_bad.py").write_text(
        "import jax\n\ndef f():\n    g = jax.jit(lambda v: v)\n    return g\n"
    )
    out = _cli(["--format", "json", "--changed"], cwd=str(tmp_path))
    doc = json.loads(out.stdout)
    assert out.returncode == 1
    assert doc["files_scanned"] == 1
    assert {f["path"] for f in doc["findings"]} == {"pkg/new_bad.py"}
    # a tracked modification is in scope too, and positional paths scope
    # the changed set
    (pkg / "clean.py").write_text("import jax\n\njnp = jax.numpy\nx = 1\n")
    out = _cli(["--format", "json", "--changed"], cwd=str(tmp_path))
    assert json.loads(out.stdout)["files_scanned"] == 2
    # scope paths normalize: ./pkg scopes the same files as pkg
    out = _cli(["--format", "json", "./pkg", "--changed"], cwd=str(tmp_path))
    assert json.loads(out.stdout)["files_scanned"] == 2
    other = tmp_path / "other"
    other.mkdir()
    # positional paths precede --changed (a path AFTER a bare --changed
    # would parse as its BASE argument; --changed=BASE disambiguates)
    out = _cli(
        ["--format", "json", "other", "--changed"], cwd=str(tmp_path)
    )
    doc = json.loads(out.stdout)
    assert out.returncode == 0 and doc["files_scanned"] == 0


def test_changed_mode_unknown_base_is_config_error(tmp_path):
    (tmp_path / "a.py").write_text("x = 1\n")
    assert _git(tmp_path, "init", "-q").returncode == 0
    out = _cli(["--changed", "no-such-ref"], cwd=str(tmp_path))
    assert out.returncode == 2
    assert "merge-base" in out.stderr


# ---------------------------------------------------------------------------
# Wire-parity runtime anchor: the registry the GL1002 pass reads must map
# every wire-decodable aggregator to a function _agg_one implements
# ---------------------------------------------------------------------------


_WIRE_AGG_SPECS = [
    {"type": "count", "name": "n"},
    {"type": "longSum", "name": "a", "fieldName": "v"},
    {"type": "doubleSum", "name": "a", "fieldName": "v"},
    {"type": "longMin", "name": "a", "fieldName": "v"},
    {"type": "doubleMin", "name": "a", "fieldName": "v"},
    {"type": "longMax", "name": "a", "fieldName": "v"},
    {"type": "doubleMax", "name": "a", "fieldName": "v"},
    {"type": "hyperUnique", "name": "a", "fieldName": "v"},
    {"type": "cardinality", "name": "a", "fields": ["v"]},
    {"type": "thetaSketch", "name": "a", "fieldName": "v"},
    {"type": "quantilesDoublesSketch", "name": "a", "fieldName": "v"},
    {"type": "dimCodeMax", "name": "a", "fieldName": "v"},
    {
        "type": "filtered",
        "filter": {"type": "selector", "dimension": "v", "value": "1"},
        "aggregator": {"type": "longSum", "name": "a", "fieldName": "v"},
    },
    {"type": "javascript", "name": "a", "expression": "v * 2"},
]


def test_wire_agg_fallback_registry_is_complete_and_executable():
    import pandas as pd

    from spark_druid_olap_tpu.exec.fallback import (
        _agg_one,
        fallback_agg_fn,
    )
    from spark_druid_olap_tpu.models.wire import agg_from_druid
    from spark_druid_olap_tpu.plan import logical as L
    from spark_druid_olap_tpu.plan.expr import Col

    df = pd.DataFrame({"v": [1.0, 2.0, 2.0, 4.0]})
    for spec in _WIRE_AGG_SPECS:
        agg = agg_from_druid(spec)
        fn = fallback_agg_fn(agg)  # raises on a registry gap
        ae = L.AggExpr(
            name="a", fn=fn, arg=Col("v"),
            args=(0.5,) if fn == "approx_quantile" else (),
        )
        out = _agg_one(ae, df)  # raises if _agg_one lacks the function
        assert out == out, (spec, fn)  # not NaN for non-empty input


def test_cli_update_baseline_grandfathers_and_then_passes(tmp_path):
    pkg = tmp_path / "pkg"
    pkg.mkdir()
    (pkg / "bad.py").write_text(
        "import jax\n\njax.config.update(\"jax_enable_x64\", True)\n"
    )
    assert _cli(["pkg"], cwd=str(tmp_path)).returncode == 1
    out = _cli(["--update-baseline", "pkg"], cwd=str(tmp_path))
    assert out.returncode == 0, out.stdout + out.stderr
    entries = load_baseline(str(tmp_path / "graftlint_baseline.json"))
    assert len(entries) == 1 and entries[0].code == "GL402"
    # grandfathered: the gate passes now
    assert _cli(["pkg"], cwd=str(tmp_path)).returncode == 0
    # fixing the violation makes the entry STALE: exit 2
    (pkg / "bad.py").write_text("import jax\n")
    out = _cli(["pkg"], cwd=str(tmp_path))
    assert out.returncode == 2
    assert "STALE" in out.stdout


# ---------------------------------------------------------------------------
# --changed reverse-dependency closure + --stats (interprocedural CLI)
# ---------------------------------------------------------------------------


def test_changed_mode_expands_reverse_dependency_closure(tmp_path):
    """Changing a module pulls its importers (transitively) into the
    lint set: the importer's findings can be created or fixed by the
    change, so the fast loop must see them."""
    pkg = tmp_path / "pkg"
    pkg.mkdir()
    (pkg / "__init__.py").write_text("")
    (pkg / "leaf.py").write_text("VALUE = 1\n")
    (pkg / "mid.py").write_text("from .leaf import VALUE\n\nM = VALUE\n")
    (pkg / "top.py").write_text("from .mid import M\n\nT = M\n")
    (pkg / "unrelated.py").write_text("x = 1\n")
    assert _git(tmp_path, "init", "-q").returncode == 0
    _git(tmp_path, "branch", "-m", "main")
    _git(tmp_path, "config", "user.email", "t@example.com")
    _git(tmp_path, "config", "user.name", "t")
    _git(tmp_path, "add", "-A")
    assert _git(tmp_path, "commit", "-qm", "seed").returncode == 0
    # touching the leaf lints leaf + mid + top, NOT unrelated
    (pkg / "leaf.py").write_text("VALUE = 2\n")
    out = _cli(["--format", "json", "--changed"], cwd=str(tmp_path))
    doc = json.loads(out.stdout)
    assert out.returncode == 0, out.stdout + out.stderr
    assert doc["files_scanned"] == 3
    # touching the top lints only the top (nothing imports it)
    _git(tmp_path, "add", "-A")
    assert _git(tmp_path, "commit", "-qm", "leaf").returncode == 0
    (pkg / "top.py").write_text("from .mid import M\n\nT = M + 1\n")
    out = _cli(["--format", "json", "--changed"], cwd=str(tmp_path))
    assert json.loads(out.stdout)["files_scanned"] == 1
    # the text banner names the expansion
    _git(tmp_path, "add", "-A")
    assert _git(tmp_path, "commit", "-qm", "top").returncode == 0
    (pkg / "leaf.py").write_text("VALUE = 3\n")
    out = _cli(["--changed"], cwd=str(tmp_path))
    assert "(+2 reverse-dependent)" in out.stdout


def test_changed_closure_finds_importer_break(tmp_path):
    """The reason the closure exists: a contract change in the edited
    file surfaces a finding in an UNCHANGED importer."""
    pkg = tmp_path / "pkg"
    pkg.mkdir()
    (pkg / "__init__.py").write_text("")
    (pkg / "helper.py").write_text("def make():\n    return None\n")
    # the importer has a latent violation graftlint attributes to ITS
    # file; a plain changed-files run would never rescan it
    (pkg / "user.py").write_text(
        "import jax\n\nfrom .helper import make\n\n"
        "def f():\n    g = jax.jit(lambda v: v)\n    return g, make()\n"
    )
    assert _git(tmp_path, "init", "-q").returncode == 0
    _git(tmp_path, "branch", "-m", "main")
    _git(tmp_path, "config", "user.email", "t@example.com")
    _git(tmp_path, "config", "user.name", "t")
    _git(tmp_path, "add", "pkg/__init__.py", "pkg/helper.py")
    assert _git(tmp_path, "commit", "-qm", "seed").returncode == 0
    # user.py is committed separately so only helper.py "changes"...
    _git(tmp_path, "add", "-A")
    assert _git(tmp_path, "commit", "-qm", "user").returncode == 0
    (pkg / "helper.py").write_text("def make():\n    return 1\n")
    out = _cli(["--format", "json", "--changed"], cwd=str(tmp_path))
    doc = json.loads(out.stdout)
    assert out.returncode == 1
    assert "pkg/user.py" in {f["path"] for f in doc["findings"]}


def test_stats_emits_machine_readable_summary(tmp_path):
    pkg = tmp_path / "pkg"
    pkg.mkdir()
    (pkg / "m.py").write_text("x = 1\n")
    # text mode: one-line JSON after the summary
    out = _cli(["--stats", "pkg"], cwd=str(tmp_path))
    assert out.returncode == 0, out.stdout + out.stderr
    line = [
        l for l in out.stdout.splitlines()
        if l.startswith("graftlint --stats ")
    ]
    assert len(line) == 1
    doc = json.loads(line[0][len("graftlint --stats "):])
    assert doc["files_scanned"] == 1
    assert doc["passes"] == len(ALL_PASSES)
    assert doc["findings_new"] == 0
    assert doc["total_seconds"] >= 0
    assert "core:parse+project" in doc["per_pass_seconds"]
    assert set(doc["per_pass_seconds"]) >= {
        cls.name for cls in ALL_PASSES
    }
    # json mode: same object embedded under "stats"
    out = _cli(["--stats", "--json", "pkg"], cwd=str(tmp_path))
    full = json.loads(out.stdout)
    assert full["stats"]["files_scanned"] == 1
    assert full["stats"]["per_pass_findings"] == {}


def test_stats_counts_findings_per_pass(tmp_path):
    pkg = tmp_path / "pkg"
    pkg.mkdir()
    (pkg / "bad.py").write_text(
        "import jax\n\njax.config.update(\"jax_enable_x64\", True)\n"
    )
    out = _cli(["--stats", "--json", "pkg"], cwd=str(tmp_path))
    doc = json.loads(out.stdout)
    assert doc["stats"]["per_pass_findings"] == {"compat-import": 1}
    assert doc["stats"]["findings_new"] == 1


def test_whole_tree_stats_meets_work_budget_acceptance():
    """The ISSUE 17 acceptance criterion (the full project run under 10 s
    via --stats), held across every pass generation since (ISSUE 20 lands
    the 29th), as a budget of WORK and not of wall clock: tier-1 runs this
    beside five other busy xdist workers, and a 10 s wall limit failed
    there on a tree that had not changed (ledger, PRs 22 and 23).  The
    yardstick is the run's own parse of the same files, in the same
    process under the same load: when the criterion was written the parse
    took 1.16 s of the 10, so the 29 passes together may cost 7.6 parses.
    The parse's own work is a count, bounded beside it."""
    out = _cli(["--stats", *_TARGETS], cwd=_ROOT)
    assert out.returncode == 0, out.stdout + out.stderr
    line = [
        l for l in out.stdout.splitlines()
        if l.startswith("graftlint --stats ")
    ][0]
    doc = json.loads(line[len("graftlint --stats "):])
    assert doc["passes"] == len(ALL_PASSES) == 29
    assert doc["findings_new"] == 0
    assert 150 <= doc["files_scanned"] <= 300
    per = dict(doc["per_pass_seconds"])
    parse = per.pop("core:parse+project")
    assert len(per) == 29 and sum(per.values()) < 7.6 * parse, per


def test_baseline_has_no_superseded_lock_entries():
    """ISSUE 17 satellite: GL25xx sees lock ownership precisely, so the
    baseline must not (re)grow grandfathered GL5xx/GL14xx lock findings
    — every lock-discipline violation is either fixed or carried by the
    interprocedural pass's own codes with a justification."""
    entries = load_baseline(
        os.path.join(_ROOT, "graftlint_baseline.json")
    )
    superseded = [
        e for e in entries
        if e.pass_name in ("lock-discipline", "lock-order")
        or e.code.startswith("GL5") or e.code.startswith("GL14")
    ]
    assert superseded == [], [
        (e.path, e.pass_name, e.code) for e in superseded
    ]


# ---------------------------------------------------------------------------
# Resource/flow acceptance (merged from the former test_lint_v3.py)
# ---------------------------------------------------------------------------

# one kernel, ~64 MiB resident (2 refs x 2048x2048 f32, double-buffered):
# over a 16 MiB TPU budget, comfortably under a 1 GiB CPU bound
_BIG_TILE_KERNEL = """
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    BLOCK = 2048

    def _sum_kernel(x_ref, o_ref):
        o_ref[:] = x_ref[:] + 1.0

    def run(x):
        return pl.pallas_call(
            _sum_kernel,
            grid=(4,),
            in_specs=[pl.BlockSpec((BLOCK, BLOCK), lambda i: (i, 0))],
            out_specs=pl.BlockSpec((BLOCK, BLOCK), lambda i: (i, 0)),
            out_shape=jax.ShapeDtypeStruct((8192, 2048), jnp.float32),
        )(x)
"""


def _budget_run(tmp_path, platform):
    return run_lint(
        str(tmp_path), ["pkg"], pass_names=["resource-budget"],
        config_overrides={"resource-budget": {"platform": platform}},
    )


def test_budget_pass_honors_per_platform_calibration(tmp_path):
    """Dual-calibration golden: the SAME kernel gets DIFFERENT verdicts
    under calibration.tpu.json (16 MiB) vs calibration.cpu.json (1 GiB)
    — the pass reads the calibrated config, not a baked-in constant."""
    _write_tree(tmp_path, {"pkg/kern.py": _BIG_TILE_KERNEL})
    (tmp_path / "calibration.tpu.json").write_text(
        json.dumps({"vmem_budget_bytes": 16 * 1024 * 1024})
    )
    (tmp_path / "calibration.cpu.json").write_text(
        json.dumps({"vmem_budget_bytes": 1024 * 1024 * 1024})
    )
    tpu = _budget_run(tmp_path, "tpu")
    assert {f.code for f in tpu.new} == {"GL1201"}
    assert "calibration.tpu.json" in tpu.new[0].message
    cpu = _budget_run(tmp_path, "cpu")
    assert cpu.new == [], [f.render() for f in cpu.new]


def test_repo_calibration_files_carry_vmem_budgets():
    """The committed sidecars really carry the key the pass reads."""
    for name, expect_le in (
        ("calibration.tpu.json", 64 * 1024 * 1024),
        ("calibration.cpu.json", 4 * 1024 * 1024 * 1024),
    ):
        with open(os.path.join(_ROOT, name)) as f:
            doc = json.load(f)
        assert doc.get("vmem_budget_bytes", 0) > 0, name
        assert doc["vmem_budget_bytes"] <= expect_le, name
    # and the TPU budget is the binding one (smaller than CPU's)
    with open(os.path.join(_ROOT, "calibration.tpu.json")) as f:
        tpu = json.load(f)["vmem_budget_bytes"]
    with open(os.path.join(_ROOT, "calibration.cpu.json")) as f:
        cpu = json.load(f)["vmem_budget_bytes"]
    assert tpu < cpu


def test_budget_falls_back_to_scanned_config_default(tmp_path):
    _write_tree(tmp_path, {
        "pkg/kern.py": _BIG_TILE_KERNEL,
        # a scanned config module declaring a 1 GiB-class budget: the
        # kernel passes; with 1 MiB it fails — no calibration file here
        "spark_druid_olap_tpu/config.py": """
            class SessionConfig:
                vmem_budget_mb: int = 1024
        """,
    })
    res = run_lint(
        str(tmp_path), ["."], pass_names=["resource-budget"],
    )
    assert res.new == [], [f.render() for f in res.new]
    (tmp_path / "spark_druid_olap_tpu" / "config.py").write_text(
        "class SessionConfig:\n    vmem_budget_mb: int = 1\n"
    )
    res = run_lint(
        str(tmp_path), ["."], pass_names=["resource-budget"],
    )
    assert {f.code for f in res.new} == {"GL1201"}
    assert "vmem_budget_mb" in res.new[0].message


def test_budget_builtin_default_when_nothing_configured(tmp_path):
    _write_tree(tmp_path, {"pkg/kern.py": _BIG_TILE_KERNEL})
    res = _budget_run(tmp_path, "tpu")
    assert {f.code for f in res.new} == {"GL1201"}
    assert "built-in" in res.new[0].message


_DEPTH2_FIXTURE = {
    "spark_druid_olap_tpu/exec/engine.py": """
        from ..resilience import checkpoint

        def _note(seg):
            _really_checkpoint(seg)

        def _really_checkpoint(seg):
            checkpoint("engine.segment_loop")

        def scan(segs):
            out = []
            for seg in segs:
                out.append(_note(seg))
            return out
    """,
}


def test_flow_layer_depth_two_call_through(tmp_path):
    """A checkpoint two helpers down: a GL901 finding under the default
    one-level contract, clean when the pass config deepens the flow
    query to 2 — the depth is configurable AND actually honored."""
    v1 = tmp_path / "d1"
    _write_tree(v1, _DEPTH2_FIXTURE)
    res = run_lint(str(v1), ["."], pass_names=["checkpoint-coverage"])
    assert {f.code for f in res.new} == {"GL901"}
    v2 = tmp_path / "d2"
    _write_tree(v2, _DEPTH2_FIXTURE)
    res = run_lint(
        str(v2), ["."], pass_names=["checkpoint-coverage"],
        config_overrides={
            "checkpoint-coverage": {"call_through_depth": 2},
        },
    )
    assert res.new == [], [f.render() for f in res.new]


def test_const_eval_arithmetic_and_minmax(tmp_path):
    project = _project_of(tmp_path, {
        "pkg/consts.py": "BLOCK = 1024\nPAD = 128\n",
        "pkg/use.py": "from .consts import BLOCK\n\nLOCAL = BLOCK // 2\n",
    })
    ev = lambda s, env=None: _eval_in(project, "pkg/use.py", s, env)  # noqa: E731
    assert ev("BLOCK") == 1024
    assert ev("LOCAL") == 512
    assert ev("min(BLOCK, 4096) + max(1, 2)") == 1026
    assert ev("-(-1030 // BLOCK) * BLOCK") == 2048  # ceil-round idiom
    assert ev("(BLOCK, LOCAL // 4)") == (1024, 128)
    assert ev("BLOCK if LOCAL > 100 else 0") == 1024
    assert ev("unknown_name") is None
    assert ev("BLOCK // unknown_name") is None
    assert ev("block_rows", {"block_rows": 256}) == 256


def test_const_eval_class_defaults_cross_module(tmp_path):
    project = _project_of(tmp_path, {
        "pkg/config.py": (
            "class SessionConfig:\n"
            "    vmem_budget_mb: int = 16\n"
            "    slots = 4\n"
        ),
        "pkg/use.py": (
            "from .config import SessionConfig\n"
        ),
    })
    assert _eval_in(
        project, "pkg/use.py", "SessionConfig.vmem_budget_mb * 1024"
    ) == 16 * 1024
    assert _eval_in(project, "pkg/config.py", "SessionConfig.slots") == 4


def test_profile_reports_per_pass_timings(tmp_path):
    (tmp_path / "pkg").mkdir()
    (tmp_path / "pkg" / "m.py").write_text("x = 1\n")
    out = _cli(["--profile", "pkg"], cwd=str(tmp_path))
    assert out.returncode == 0, out.stdout + out.stderr
    assert "per-pass seconds" in out.stdout
    assert "core:parse+project" in out.stdout
    assert "total" in out.stdout


def test_whole_tree_lint_stays_within_time_budget():
    """A pass that regresses to whole-tree quadratic shows up HERE, not
    as a mysteriously slow CI.  Budget: 30 s wall (the 25-pass run
    measures ~5 s on this container; CI-noise headroom on top of the
    10 s --stats acceptance bound)."""
    t0 = time.monotonic()
    res = run_lint(_ROOT, _TARGETS, profile=True)
    elapsed = time.monotonic() - t0
    assert elapsed < 30.0, (
        f"whole-tree lint took {elapsed:.1f}s (budget 30s); "
        f"per-pass: {sorted(res.timings.items(), key=lambda kv: -kv[1])}"
    )
    # the profile accounting covers the passes that actually ran
    assert "core:parse+project" in res.timings
    assert set(res.pass_names) <= set(res.timings) | {"core"}


def test_update_baseline_prints_diff_summary(tmp_path):
    pkg = tmp_path / "pkg"
    pkg.mkdir()
    (pkg / "a.py").write_text(
        "import jax\n\njax.config.update(\"jax_enable_x64\", True)\n"
    )
    out = _cli(["--update-baseline", "pkg"], cwd=str(tmp_path))
    assert out.returncode == 0, out.stdout + out.stderr
    assert "(1 added, 0 removed, 0 carried)" in out.stdout
    assert "+ pkg/a.py [compat-import/GL402]" in out.stdout
    # second violation: one added, one carried
    (pkg / "b.py").write_text(
        "import jax\n\ndef f():\n    g = jax.jit(lambda v: v)\n    return g\n"
    )
    out = _cli(["--update-baseline", "pkg"], cwd=str(tmp_path))
    assert "(1 added, 0 removed, 1 carried)" in out.stdout
    assert "+ pkg/b.py [jit-cache/GL101]" in out.stdout
    # fixing a violation: its entry is reported removed
    (pkg / "a.py").write_text("import jax\n")
    out = _cli(["--update-baseline", "pkg"], cwd=str(tmp_path))
    assert "(0 added, 1 removed, 1 carried)" in out.stdout
    assert "- pkg/a.py [compat-import/GL402]" in out.stdout
    # and the resulting baseline still gates clean
    assert _cli(["pkg"], cwd=str(tmp_path)).returncode == 0


def test_lock_order_depth_zero_sees_only_lexical_nesting(tmp_path):
    files = {
        "spark_druid_olap_tpu/exec/locks.py": """
            import threading

            _A_LOCK = threading.Lock()
            _B_LOCK = threading.Lock()

            def a_then_b():
                with _A_LOCK:
                    _take_b()

            def b_then_a():
                with _B_LOCK:
                    _take_a()

            def _take_a():
                with _A_LOCK:
                    pass

            def _take_b():
                with _B_LOCK:
                    pass
        """,
    }
    v1 = tmp_path / "deep"
    _write_tree(v1, files)
    res = run_lint(str(v1), ["."], pass_names=["lock-order"])
    assert {f.code for f in res.new} == {"GL1401"}
    v2 = tmp_path / "shallow"
    _write_tree(v2, files)
    res = run_lint(
        str(v2), ["."], pass_names=["lock-order"],
        config_overrides={"lock-order": {"call_depth": 0}},
    )
    assert res.new == [], [f.render() for f in res.new]
