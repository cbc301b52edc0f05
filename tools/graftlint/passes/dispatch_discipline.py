"""dispatch-discipline pass: device dispatch is a budget, not a loop
body (GL21xx, ISSUE 14 satellite).

The one-dispatch arena (spark_druid_olap_tpu/exec/arena.py) collapsed
the executor's per-segment dispatch loop into a single traced `lax.scan`
program: dispatch count is now an O(1) property the cost receipts
surface (`dispatch_count`) and bench counterfactuals assert on.  That
property only survives if new code doesn't quietly reintroduce
per-item host loops around the device boundary.  This pass polices the
two ways it regresses:

* **GL2101 — dispatch span opened inside a host loop.**  A
  `span(SPAN_SEGMENT_DISPATCH, ...)` (or any dispatch-bucket span: the
  sparse/adaptive/stream/collective families) inside a Python
  `for`/`while` in exec// serve/ is a per-iteration device round-trip —
  exactly the O(segments) pattern the arena exists to collapse.  The
  sanctioned loop owners (the fold remainder loops, the arena's chunk
  loop, the sparse/adaptive/streaming executors whose batch loops are
  deadline-checkpointed by design) are allow-listed by function name;
  anything else must either ride the arena or add itself to the allow
  list with a justification.
* **GL2102 — `jax.jit` constructed inside a host loop.**  Building the
  transform per iteration discards the traced program each pass: every
  iteration retraces and recompiles, the program cache (and its
  `sdol_program_cache_total` attribution) never hits, and compile time
  is silently re-paid O(n) times.  Programs are built once in a cached
  builder (`_segment_program` / `build_arena_program`) and *called* in
  loops.

Both checks are frame-local (a closure defined under a loop does not
RUN under it — same contract as lock-discipline) and scoped to
exec// serve/: parallel/ keeps its own sharded-dispatch contract.
"""

from __future__ import annotations

import ast

from ..core import LintPass, ModuleContext, dotted_name, is_jit_callee

# span-name constants (and their runtime string names) whose spans time
# a device dispatch — the receipt's dispatch_count buckets
_DISPATCH_SPANS = frozenset({
    "SPAN_SEGMENT_DISPATCH", "SPAN_SPARSE_DISPATCH", "SPAN_ADAPTIVE_PROBE",
    "SPAN_STREAM_CHUNK",
    "segment_dispatch", "sparse_dispatch", "adaptive_probe",
    "stream_chunk",
})


class DispatchDisciplinePass(LintPass):
    name = "dispatch-discipline"
    default_config = {
        # the executor + serving trees; parallel/ is excluded (mesh
        # shard dispatch has its own collective contract)
        "include": (
            "spark_druid_olap_tpu/exec/",
            "spark_druid_olap_tpu/serve/",
        ),
        "allow_files": (),
        # sanctioned dispatch-loop owners.  Checked against the WHOLE
        # enclosing-function stack so their helper closures (fold
        # callbacks, presence probes) stay covered.
        "allow_funcs": (
            # engine remainder loops: canonical fold over the batches
            # the arena declined (non-uniform shapes, over-budget tail)
            "_partials_for_query",
            "execute_fused",
            "execute_progressive",
            # the arena's own chunk loop: one iteration per anytime
            # checkpoint, not per segment
            "run_plan",
            # sparse/adaptive/streaming executors: batch loops are
            # deadline-checkpointed by design (checkpoint-coverage)
            "_dispatch_groupby_sparse",
            "_adaptive_kept_codes",
            "_execute_groupby",
        ),
    }

    def _in_scope(self, ctx: ModuleContext) -> bool:
        if any(
            ctx.relpath.startswith(p) for p in self.config["allow_files"]
        ):
            return False
        if not any(
            ctx.relpath.startswith(p) for p in self.config["include"]
        ):
            return False
        allow = tuple(self.config["allow_funcs"])
        return not any(
            getattr(f, "name", "") in allow for f in ctx.scope.func_stack
        )

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
        if not ctx.scope.in_loop:
            return
        if self._is_dispatch_span(node):
            if self._in_scope(ctx):
                self.report(
                    ctx, node, "GL2101",
                    "dispatch span inside a host loop is a per-iteration "
                    "device round-trip — the O(segments) pattern the "
                    "one-dispatch arena collapsed; route the scope "
                    "through exec.arena (one lax.scan program) or add "
                    "the loop owner to dispatch-discipline allow_funcs "
                    "with a justification",
                )
            return
        # node.func covers `jax.jit(fn)`; node itself covers the
        # `functools.partial(jax.jit, ...)` spelling
        if (
            is_jit_callee(node.func) or is_jit_callee(node)
        ) and self._in_scope(ctx):
            self.report(
                ctx, node, "GL2102",
                "jax.jit constructed inside a host loop retraces and "
                "recompiles every iteration and can never hit the "
                "program cache — build the program once in a cached "
                "builder (engine._segment_program / "
                "arena.build_arena_program) and call it in the loop",
            )
