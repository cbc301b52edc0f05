"""Trace-purity pass: side effects and host syncs where tracing happens.

A `@jax.jit`/pallas-kernel body executes ONCE at trace time; side effects
inside it silently freeze (a `time.time()` call becomes a constant, an
I/O call happens at compile time, a global mutation happens once), and
host syncs (`.item()`, `np.asarray` on a tracer) either error or force a
device round trip per call.  Checks:

* **GL201** — `global` declaration inside a traced function (trace-time
  mutation of module state: runs once, not per call).
* **GL202** — impure call inside a traced function: `time.*`,
  `np.random.*`/`random.*` (traced randomness must go through
  `jax.random`), `open`/`print`/`input`, `os.environ`/`os.getenv`.
* **GL203** — host materialization inside a traced function: `.item()`,
  `np.asarray`/`np.array`, `jax.device_get`, `np.frombuffer` — on a
  tracer these raise `TracerArrayConversionError` or silently constant-
  fold at trace time.
* **GL204** — host sync in a hot loop: `.item()` / `jax.device_get`
  inside a `for`/`while` body in the configured hot execution modules
  (the engine segment loop, the streaming chunk loop, the SPMD
  dispatchers).  Each sync is a full device round trip, multiplied by
  the loop trip count.

Traced scope = lexically inside a function with a jit decorator (incl.
`functools.partial(jax.jit, ...)`) or a function whose name matches the
configured kernel suffixes (Pallas kernels are invoked via
`pl.pallas_call`, not a decorator).
"""

from __future__ import annotations

import ast

from ..core import (
    LintPass,
    ModuleContext,
    call_name,
    dotted_name,
    has_jit_decorator,
)

_IMPURE_PREFIXES = (
    "time.", "np.random.", "numpy.random.", "random.", "os.path.",
)
_IMPURE_EXACT = {
    "open", "print", "input", "os.environ", "os.getenv", "time.time",
    "random.random",
}
_HOST_SYNC_CALLS = {
    "np.asarray", "numpy.asarray", "np.array", "numpy.array",
    "jax.device_get", "np.frombuffer", "numpy.frombuffer",
}


class TracePurityPass(LintPass):
    name = "trace-purity"
    default_config = {
        "kernel_name_suffixes": ("_kernel",),
        # host syncs inside loops are flagged only on the hot execution
        # paths — the pandas fallback interpreter and finalization are
        # host-side by design
        "hot_loop_paths": (
            "spark_druid_olap_tpu/exec/engine.py",
            "spark_druid_olap_tpu/exec/streaming.py",
            "spark_druid_olap_tpu/exec/sparse_exec.py",
            "spark_druid_olap_tpu/exec/adaptive_exec.py",
            "spark_druid_olap_tpu/parallel/distributed.py",
        ),
    }

    def _is_traced(self, func: ast.AST) -> bool:
        if has_jit_decorator(func):
            return True
        name = getattr(func, "name", "")
        return any(
            name.endswith(sfx) or name == sfx.lstrip("_")
            for sfx in self.config["kernel_name_suffixes"]
        )

    def _in_traced_scope(self, ctx: ModuleContext) -> bool:
        return any(self._is_traced(f) for f in ctx.scope.func_stack)

    # -- GL201 ----------------------------------------------------------------

    def on_Global(self, node: ast.Global, ctx: ModuleContext):
        if self._in_traced_scope(ctx):
            self.report(
                ctx, node, "GL201",
                f"`global {', '.join(node.names)}` inside a traced function "
                "mutates module state at TRACE time (once), not per call",
            )

    # -- GL202 / GL203 / GL204 -----------------------------------------------

    def on_Call(self, node: ast.Call, ctx: ModuleContext):
        dn = call_name(node)
        traced = self._in_traced_scope(ctx)
        if traced:
            if dn in _IMPURE_EXACT or any(
                dn.startswith(p) for p in _IMPURE_PREFIXES
            ):
                self.report(
                    ctx, node, "GL202",
                    f"impure call {dn}() inside a traced function executes "
                    "once at trace time and freezes into the compiled "
                    "program (use jax.random / hoist I-O out of jit)",
                )
                return
            if dn in _HOST_SYNC_CALLS:
                self.report(
                    ctx, node, "GL203",
                    f"{dn}() inside a traced function materializes on host: "
                    "on a tracer this raises or constant-folds at trace "
                    "time — keep traced code in jnp",
                )
                return
            if self._is_item_call(node):
                self.report(
                    ctx, node, "GL203",
                    ".item() inside a traced function forces host "
                    "materialization — keep traced code in jnp",
                )
                return
        # GL204: host sync in a hot loop (host-side code)
        if (
            not traced
            and ctx.scope.in_loop
            and ctx.relpath in self.config["hot_loop_paths"]
        ):
            if dn == "jax.device_get" or self._is_item_call(node):
                what = "jax.device_get" if dn == "jax.device_get" else ".item()"
                self.report(
                    ctx, node, "GL204",
                    f"{what} inside a loop on a hot execution path: one "
                    "blocking device round trip PER ITERATION — batch the "
                    "fetch outside the loop or justify it in the baseline",
                )

    @staticmethod
    def _is_item_call(node: ast.Call) -> bool:
        return (
            isinstance(node.func, ast.Attribute)
            and node.func.attr == "item"
            and not node.args
            and not node.keywords
        )

    def on_Attribute(self, node: ast.Attribute, ctx: ModuleContext):
        # os.environ subscript/read inside traced scope (not a call)
        if dotted_name(node) == "os.environ" and self._in_traced_scope(ctx):
            self.report(
                ctx, node, "GL202",
                "os.environ read inside a traced function freezes the "
                "env value at trace time",
            )
