"""mesh-discipline pass: the unified SPMD core's placement and
collective contracts (GL22xx, ISSUE 15 satellite).

The unified executor core (parallel/spmd_arena.py + DistributedEngine)
rests on three disciplines that an innocent-looking edit can silently
break long before any multi-device CI runs:

* **GL2201 — collective axis named by a string literal.**  Mesh axis
  names are declared ONCE (`parallel/mesh.py`: `DATA_AXIS`,
  `GROUPS_AXIS`, `SLICE_AXIS`) and consumed by reference; a literal
  `lax.psum(x, "data")` type-checks, runs, and merges over the right
  axis — until the axis layout changes (exactly what the multi-slice
  topology did) and the literal keeps naming the OLD world.  The
  collective-axis pass (GL801) catches names no mesh declares; this
  check catches the sneakier case where the literal IS a declared name
  and therefore never fails, it just stops meaning what the author
  thought.  Scope: the runtime package (fixtures/tests exercise
  literals deliberately).
* **GL2202 — shard placement outside the sanctioned owners.**  In
  parallel/, every host->device placement with an explicit sharding or
  device rides a named owner (`multihost.put_sharded`, the engine's
  `_place_shards` / `_global_columns` / `_place_arena`,
  `spmd_arena.init_carry_stacked`): those own the residency keys, h2d
  fault site, link accounting, and the multi-process placement shim.  A
  bare `jax.device_put(x, sharding)` elsewhere bypasses all four — the
  mesh-side analog of transfer-discipline's GL1901, which deliberately
  excludes parallel/ in deference to this contract.
* **GL2203 — per-shard dispatch loop on the SPMD path.**  The whole
  point of the sharded arena is ONE dispatch per query: a
  dispatch-family span inside a host `for`/`while` in parallel/ is the
  O(shards) round-trip pattern the unified core exists to collapse.
  The chunked anytime mode (`_arena_spmd_deadline`: one iteration per
  deadline checkpoint, not per shard) is the sanctioned owner.

All checks are frame-local (same contract as dispatch-discipline);
allow lists are checked against the whole enclosing-function stack so
helper closures inside an owner stay covered.
"""

from __future__ import annotations

import ast

from ..core import LintPass, ModuleContext, dotted_name

# collective -> index of the positional axis-name argument (the same
# family collective-axis checks; axis_index takes the axis first)
_COLLECTIVES = {
    "psum": 1, "pmin": 1, "pmax": 1, "pmean": 1,
    "all_gather": 1, "psum_scatter": 1, "all_to_all": 1,
    "axis_index": 0,
}

_DISPATCH_SPANS = frozenset({
    "SPAN_SEGMENT_DISPATCH", "SPAN_SPARSE_DISPATCH", "SPAN_ADAPTIVE_PROBE",
    "SPAN_STREAM_CHUNK",
    "segment_dispatch", "sparse_dispatch", "adaptive_probe",
    "stream_chunk",
})


def _collective_short(canon: str) -> str:
    """Short collective name when `canon` is a lax collective, else ''."""
    short = canon.rsplit(".", 1)[-1]
    if short not in _COLLECTIVES:
        return ""
    if canon in (short, f"lax.{short}", f"jax.lax.{short}") or (
        canon.endswith(f".lax.{short}")
    ):
        return short
    return ""


def _is_device_put(canon: str) -> bool:
    return canon == "device_put" or canon.endswith(".device_put")


class MeshDisciplinePass(LintPass):
    name = "mesh-discipline"
    default_config = {
        # GL2201: the whole runtime package (axis constants are a
        # package-wide contract); tests/tools stay out of scope
        "axis_include": ("spark_druid_olap_tpu/",),
        # GL2202 + GL2203: the mesh tree, where the unified core's
        # placement/dispatch ownership lives
        "include": ("spark_druid_olap_tpu/parallel/",),
        "allow_files": (),
        # sanctioned placement owners (GL2202): these hold the residency
        # keys, fire the h2d fault site, and record link accounting
        "place_funcs": (
            "put_sharded",
            "_place_shards",
            "_place_arena",
            "_global_columns",
            "init_carry_stacked",
        ),
        # sanctioned dispatch-loop owners (GL2203): the chunked anytime
        # mode iterates per deadline checkpoint, and the sparse ladder
        # per capacity-escalation rung — neither is per-shard
        "loop_funcs": ("_arena_spmd_deadline", "_execute_sparse"),
    }

    def _in_tree(self, ctx: ModuleContext, include_key: str) -> bool:
        if any(
            ctx.relpath.startswith(p) for p in self.config["allow_files"]
        ):
            return False
        return any(
            ctx.relpath.startswith(p) for p in self.config[include_key]
        )

    def _under(self, ctx: ModuleContext, funcs_key: str) -> bool:
        allow = tuple(self.config[funcs_key])
        return any(
            getattr(f, "name", "") in allow for f in ctx.scope.func_stack
        )

    # applies_to: no global include — each rule scopes itself (GL2201
    # covers the whole package, GL2202/03 only parallel/)
    def applies_to(self, relpath: str) -> bool:
        return True

    # -- GL2201 ---------------------------------------------------------------

    def _check_axis_literal(self, node: ast.Call, ctx, short: str) -> None:
        arg = None
        for k in node.keywords:
            if k.arg == "axis_name":
                arg = k.value
        if arg is None:
            idx = _COLLECTIVES[short]
            if len(node.args) > idx:
                arg = node.args[idx]
        if arg is None:
            return
        elts = (
            list(arg.elts)
            if isinstance(arg, (ast.Tuple, ast.List)) else [arg]
        )
        for e in elts:
            if isinstance(e, ast.Constant) and isinstance(e.value, str):
                self.report(
                    ctx, node, "GL2201",
                    f"lax.{short} over the string literal {e.value!r}: "
                    "axis names are declared once in parallel/mesh.py "
                    "(*_AXIS constants) and consumed by reference — a "
                    "literal keeps 'working' after an axis-layout change "
                    "while silently merging over the wrong scope; use "
                    "the declared constant",
                )

    # -- handlers -------------------------------------------------------------

    @staticmethod
    def _is_dispatch_span(node: ast.Call) -> bool:
        if dotted_name(node.func).split(".")[-1] != "span" or not node.args:
            return False
        arg = node.args[0]
        if isinstance(arg, ast.Name):
            return arg.id in _DISPATCH_SPANS
        if isinstance(arg, ast.Constant) and isinstance(arg.value, str):
            return arg.value in _DISPATCH_SPANS
        return False

    def on_Call(self, node: ast.Call, ctx: ModuleContext):
        canon = dotted_name(node.func)
        short = _collective_short(canon)
        if short and self._in_tree(ctx, "axis_include"):
            self._check_axis_literal(node, ctx, short)
        if not self._in_tree(ctx, "include"):
            return
        # GL2202: device_put with an explicit placement target (second
        # positional arg or device=/sharding= kwarg) outside an owner.
        # A bare device_put(x) takes the default device and is another
        # pass's business (transfer-discipline, outside parallel/).
        if _is_device_put(canon):
            placed = len(node.args) > 1 or any(
                k.arg in ("device", "sharding") for k in node.keywords
            )
            if placed and not self._under(ctx, "place_funcs"):
                self.report(
                    ctx, node, "GL2202",
                    "sharded device_put outside the sanctioned placement "
                    "owners (put_sharded / _place_shards / _place_arena / "
                    "_global_columns / init_carry_stacked) bypasses the "
                    "residency keys, the h2d fault site, link accounting, "
                    "and the multi-process placement shim — route the "
                    "move through an owner or add one with a "
                    "justification",
                )
            return
        # GL2203: dispatch span under a host loop on the SPMD path
        if (
            ctx.scope.in_loop
            and self._is_dispatch_span(node)
            and not self._under(ctx, "loop_funcs")
        ):
            self.report(
                ctx, node, "GL2203",
                "dispatch span inside a host loop on the SPMD path is a "
                "per-shard round trip — the pattern the sharded arena "
                "collapsed to one dispatch per query; route the scope "
                "through parallel/spmd_arena (one shard_mapped scan) or "
                "add the loop owner to mesh-discipline loop_funcs with a "
                "justification",
            )
