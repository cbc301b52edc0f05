"""Compat-import discipline pass.

The repo targets ONE JAX (the installed one): SPMD programs build
through `jax.shard_map(..., check_vma=False)` and the Pallas kernel
traces its grid arithmetic under `jax.enable_x64(False)`.  Checks:

* **GL401** — any import or attribute use of the retired
  `jax.experimental.shard_map` API (call `jax.shard_map`).
* **GL402** — `*.config.update("jax_enable_x64", ...)` or any use of
  `jax.enable_x64` / `jax.experimental.enable_x64` outside
  `ops/pallas_groupby.py`, the one scoped 32-bit trace (the
  package-level global enable in `__init__.py` is the single sanctioned
  exception, grandfathered in the baseline).
"""

from __future__ import annotations

import ast

from ..core import LintPass, ModuleContext, dotted_name

_X64_ATTRS = ("jax.enable_x64", "jax.experimental.enable_x64")


class CompatImportPass(LintPass):
    name = "compat-import"
    default_config = {
        "allow_paths": ("spark_druid_olap_tpu/ops/pallas_groupby.py",),
    }

    def applies_to(self, relpath: str) -> bool:
        if relpath in self.config["allow_paths"]:
            return False
        return super().applies_to(relpath)

    # -- GL401 ----------------------------------------------------------------

    def on_Import(self, node: ast.Import, ctx: ModuleContext):
        for alias in node.names:
            if alias.name.startswith("jax.experimental.shard_map"):
                self.report(
                    ctx, node, "GL401",
                    "jax.experimental.shard_map is the retired API — call "
                    "jax.shard_map(..., check_vma=False)",
                )

    def on_ImportFrom(self, node: ast.ImportFrom, ctx: ModuleContext):
        mod = node.module or ""
        if mod.startswith("jax.experimental.shard_map") or (
            mod == "jax.experimental"
            and any(a.name == "shard_map" for a in node.names)
        ):
            self.report(
                ctx, node, "GL401",
                "jax.experimental.shard_map is the retired API — call "
                "jax.shard_map(..., check_vma=False)",
            )
        if mod == "jax.experimental" and any(
            a.name == "enable_x64" for a in node.names
        ):
            self.report(
                ctx, node, "GL402",
                "jax.experimental.enable_x64 outside the kernel's scoped "
                "32-bit trace (ops/pallas_groupby.py)",
            )

    def on_Attribute(self, node: ast.Attribute, ctx: ModuleContext):
        dn = dotted_name(node)
        if dn == "jax.experimental.shard_map":
            self.report(
                ctx, node, "GL401",
                "jax.experimental.shard_map is the retired API — call "
                "jax.shard_map(..., check_vma=False)",
            )
        elif dn in _X64_ATTRS:
            self.report(
                ctx, node, "GL402",
                f"{dn} outside the kernel's scoped 32-bit trace "
                "(ops/pallas_groupby.py)",
            )

    # -- GL402 ----------------------------------------------------------------

    def on_Call(self, node: ast.Call, ctx: ModuleContext):
        fn = node.func
        if not (isinstance(fn, ast.Attribute) and fn.attr == "update"):
            return
        recv = dotted_name(fn.value)
        if not recv.endswith("config") and ".config" not in recv:
            return
        if (
            node.args
            and isinstance(node.args[0], ast.Constant)
            and node.args[0].value == "jax_enable_x64"
        ):
            self.report(
                ctx, node, "GL402",
                'config.update("jax_enable_x64", ...) outside __init__: '
                "flipping x64 mid-process invalidates every traced program "
                "and splits dtype semantics across modules",
            )
