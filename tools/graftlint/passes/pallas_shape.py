"""pallas-shape pass: BlockSpec/grid/kernel contract checks (GL7xx).

A mis-tiled Pallas kernel does not crash — it silently aggregates the
wrong rows into the wrong groups (or Mosaic rejects it only on real
hardware, long after CPU tests pass in interpret mode).  The contract
between a `pl.pallas_call` site and its kernel spans data structures the
single-file walker cannot see: the kernel function may live in another
module, its fill constants two imports away.  This pass resolves all of
it through the project symbol table and checks:

* **GL701** — a BlockSpec `index_map` whose arity differs from the grid
  rank: `grid=(gt, rt)` hands every index_map exactly two program ids;
  a `lambda i: ...` under a 2-D grid indexes with a missing coordinate.
* **GL702** — a BlockSpec whose block shape rank differs from the tuple
  its `index_map` returns: `pl.BlockSpec((br, 1), lambda j, i: (i,))`
  addresses a 2-D block with a 1-D coordinate.
* **GL703** — kernel positional ref count != len(in_specs) +
  len(out_specs) + len(scratch_shapes) (after subtracting
  `functools.partial`-bound parameters): refs and specs pair
  positionally, so a mismatch shifts EVERY operand one slot over.  A
  `*refs` kernel and a `*[...]` element of a spec list have no static
  count and are not findings.
* **GL704** — a `ref[...]` subscript / `pl.load` / `pl.store` inside
  the kernel indexing with more dimensions than the ref's BlockSpec
  block rank.
* **GL705** — a weak-typed fill constant (bare float / `±inf`,
  including one resolved through a cross-module import) fed to
  `jnp.where`/`jnp.full` inside the kernel: under x64 the select
  promotes to f64 and breaks the `out_shape` dtype contract (the seed's
  Mosaic 'func.call' operand-mismatch failure).  Same-module literal
  cases are dtype-x64/GL303's job; this code covers what only the
  project symbol table can see.

All checks stay silent when a value cannot be statically resolved —
dynamic grids or spec lists are simply out of reach, not findings.
"""

from __future__ import annotations

import ast
from typing import Dict, List, Optional, Tuple

from ..core import LintPass, ModuleContext, call_name, dotted_name

_WHERE = ("jax.numpy.where", "numpy.where", "jnp.where", "np.where",
          "jax.numpy.select", "jnp.select")
_FULL = ("jax.numpy.full", "jnp.full", "numpy.full", "np.full")
_INF_ATTRS = (
    "jnp.inf", "np.inf", "numpy.inf", "math.inf", "jax.numpy.inf",
    "jnp.nan", "np.nan", "numpy.nan", "math.nan", "jax.numpy.nan",
)


def _is_pallas_call(canon: str) -> bool:
    return canon == "pallas_call" or canon.endswith(".pallas_call")


def _is_blockspec(canon: str) -> bool:
    return canon == "BlockSpec" or canon.endswith(".BlockSpec")


class PallasShapePass(LintPass):
    name = "pallas-shape"
    default_config: dict = {}

    def begin_module(self, ctx: ModuleContext) -> None:
        self._seen: set = set()  # (kernel node id, code) dedup

    # -- static value resolution ---------------------------------------------

    def _resolve_local(self, node: ast.AST, ctx: ModuleContext):
        """Resolve a Name to the expression last assigned to it in the
        enclosing function stack (innermost first), else a module-level
        constant; non-Name nodes pass through."""
        if not isinstance(node, ast.Name):
            return node
        for func in reversed(ctx.scope.func_stack):
            found = None
            for sub in ast.walk(func):
                if isinstance(sub, ast.Assign):
                    for t in sub.targets:
                        if isinstance(t, ast.Name) and t.id == node.id:
                            found = sub.value
            if found is not None:
                return found
        module = self.project.modules.get(ctx.relpath)
        if module is not None and node.id in module.constants:
            return module.constants[node.id]
        return node

    @staticmethod
    def _seq_elts(node: ast.AST) -> Optional[List[ast.AST]]:
        if isinstance(node, (ast.Tuple, ast.List)):
            return list(node.elts)
        return None

    # -- entry ----------------------------------------------------------------

    def on_Call(self, node: ast.Call, ctx: ModuleContext):
        if self.project is None:
            return
        module = self.project.modules.get(ctx.relpath)
        if module is None:
            return
        canon = self.project.canonical(module, call_name(node))
        if not _is_pallas_call(canon):
            return
        kw = {k.arg: k.value for k in node.keywords if k.arg}

        # grid rank (int grid = rank 1; unresolvable = unknown)
        grid_rank: Optional[int] = None
        grid = self._resolve_local(kw.get("grid"), ctx) if "grid" in kw \
            else None
        if grid is not None:
            elts = self._seq_elts(grid)
            if elts is not None:
                grid_rank = len(elts)
            elif isinstance(grid, ast.Constant) and isinstance(
                grid.value, int
            ):
                grid_rank = 1

        in_ranks = self._check_specs(
            kw.get("in_specs"), grid_rank, ctx, module
        )
        out_ranks = self._check_specs(
            kw.get("out_specs"), grid_rank, ctx, module
        )

        kernel = self._kernel_info(node, ctx, module)
        if kernel is None:
            return
        kfunc, kmodule, bound_pos, bound_kw = kernel
        pos_params = [
            a.arg
            for a in (kfunc.args.posonlyargs + kfunc.args.args)
        ][bound_pos:]
        pos_params = [p for p in pos_params if p not in bound_kw]

        # a `*refs` kernel pairs with any number of specs, and a
        # `*[...]` element stands for a number of specs only the run
        # knows: the count is out of static reach, not a finding
        if kfunc.args.vararg is not None:
            in_ranks = None
        scratch = self._scratch_count(kw.get("scratch_shapes"), ctx)
        if (
            in_ranks is not None and out_ranks is not None
            and scratch is not None
        ):
            # scratch refs follow the outputs; no BlockSpec, no known rank
            out_ranks = out_ranks + [None] * scratch
            expected = len(in_ranks) + len(out_ranks)
            if len(pos_params) != expected:
                self.report(
                    ctx, node, "GL703",
                    f"kernel {kfunc.name}() takes {len(pos_params)} "
                    f"positional refs but in_specs+out_specs supply "
                    f"{expected} — refs and specs pair positionally, a "
                    "mismatch shifts every operand",
                )
                return
            ranks = dict(zip(pos_params, in_ranks + out_ranks))
            self._check_kernel_body(kfunc, kmodule, ranks)
        # out_shape dtype vs fill constants (GL705)
        self._check_fills(
            kfunc, kmodule, self._out_dtypes(kw.get("out_shape"), ctx,
                                             module),
        )

    # -- specs ----------------------------------------------------------------

    def _scratch_count(self, scratch, ctx) -> Optional[int]:
        """Number of scratch refs the kernel receives after its outputs
        (0 without `scratch_shapes`), or None when unresolvable."""
        if scratch is None:
            return 0
        elts = self._seq_elts(self._resolve_local(scratch, ctx))
        if elts is None or any(isinstance(e, ast.Starred) for e in elts):
            return None
        return len(elts)

    def _check_specs(self, specs, grid_rank, ctx, module):
        """Returns the list of block ranks (None entries = unknown), or
        None when the spec list itself is unresolvable (a `*[...]`
        element: its plain elements are still checked one by one)."""
        if specs is None:
            return None
        specs = self._resolve_local(specs, ctx)
        elts = self._seq_elts(specs)
        if elts is None:
            if isinstance(specs, ast.Call):  # single BlockSpec out_specs
                elts = [specs]
            else:
                return None
        ranks: List[Optional[int]] = []
        starred = False
        for e in elts:
            rank = None
            if isinstance(e, ast.Starred):
                starred = True
                continue
            if isinstance(e, ast.Call) and _is_blockspec(
                self.project.canonical(module, call_name(e))
            ):
                shape = e.args[0] if e.args else None
                index_map = e.args[1] if len(e.args) > 1 else None
                for k in e.keywords:
                    if k.arg == "block_shape":
                        shape = k.value
                    if k.arg == "index_map":
                        index_map = k.value
                shape_elts = (
                    self._seq_elts(shape) if shape is not None else None
                )
                if shape_elts is not None:
                    rank = len(shape_elts)
                if isinstance(index_map, ast.Lambda):
                    n_args = len(index_map.args.args)
                    if grid_rank is not None and n_args != grid_rank:
                        self.report(
                            ctx, e, "GL701",
                            f"BlockSpec index_map takes {n_args} "
                            f"argument(s) but the grid is "
                            f"{grid_rank}-dimensional — every index_map "
                            "receives exactly one program id per grid "
                            "axis",
                        )
                    ret = index_map.body
                    ret_rank = (
                        len(ret.elts) if isinstance(ret, ast.Tuple) else 1
                    )
                    if rank is not None and ret_rank != rank:
                        self.report(
                            ctx, e, "GL702",
                            f"BlockSpec block shape is {rank}-D but its "
                            f"index_map returns {ret_rank} "
                            "coordinate(s) — block addressing needs one "
                            "coordinate per block dimension",
                        )
            ranks.append(rank)
        return None if starred else ranks

    # -- kernel resolution ----------------------------------------------------

    def _kernel_info(self, node: ast.Call, ctx, module):
        """(FunctionDef, owning ModuleInfo, partial-bound positional
        count, partial-bound keyword names) for the pallas_call kernel,
        or None when unresolvable."""
        if not node.args:
            return None
        kernel = self._resolve_local(node.args[0], ctx)
        bound_pos, bound_kw = 0, set()
        if isinstance(kernel, ast.Call):
            if self.project.canonical(
                module, call_name(kernel)
            ) not in ("functools.partial", "partial"):
                return None
            if not kernel.args:
                return None
            bound_pos = len(kernel.args) - 1
            bound_kw = {k.arg for k in kernel.keywords if k.arg}
            kernel = kernel.args[0]
        # raw spelling, NOT dotted_name: that helper strips a leading
        # underscore (for `import x as _x` aliases), which would turn
        # `_kernel` into an unresolvable `kernel`
        dn = kernel.id if isinstance(kernel, ast.Name) else (
            dotted_name(kernel)
        )
        fi = self.project.resolve_function(module, dn)
        if fi is None or not isinstance(
            fi.node, (ast.FunctionDef, ast.AsyncFunctionDef)
        ):
            return None
        # partial kwargs that bind KEYWORD-ONLY params do not consume
        # positional slots
        kwonly = {a.arg for a in fi.node.args.kwonlyargs}
        bound_kw -= kwonly
        return fi.node, fi.module, bound_pos, bound_kw

    # -- kernel body: subscript ranks (GL704) ---------------------------------

    def _check_kernel_body(self, kfunc, kmodule, ranks: Dict[str, int]):
        known = {p: r for p, r in ranks.items() if r is not None}
        if not known:
            return
        kctx = kmodule.ctx
        for sub in ast.walk(kfunc):
            name, n_idx, site = None, None, None
            if isinstance(sub, ast.Subscript) and isinstance(
                sub.value, ast.Name
            ):
                name, site = sub.value.id, sub
                n_idx = (
                    len(sub.slice.elts)
                    if isinstance(sub.slice, ast.Tuple)
                    else 1
                )
            elif isinstance(sub, ast.Call):
                canon = self.project.canonical(kmodule, call_name(sub))
                if (
                    canon.endswith(".load") or canon.endswith(".store")
                ) and len(sub.args) >= 2 and isinstance(
                    sub.args[0], ast.Name
                ):
                    name, site = sub.args[0].id, sub
                    idx = sub.args[1]
                    n_idx = (
                        len(idx.elts)
                        if isinstance(idx, ast.Tuple)
                        else 1
                    )
            if name is None or name not in known:
                continue
            if n_idx > known[name] and (id(site), "GL704") not in self._seen:
                self._seen.add((id(site), "GL704"))
                self.report(
                    kctx, site, "GL704",
                    f"ref {name!r} is addressed with {n_idx} indices but "
                    f"its BlockSpec block is {known[name]}-D — the extra "
                    "index reads outside the tiled block",
                )

    # -- kernel body: weak fills vs out_shape dtype (GL705) -------------------

    def _out_dtypes(self, out_shape, ctx, module) -> List[str]:
        if out_shape is None:
            return []
        out_shape = self._resolve_local(out_shape, ctx)
        elts = self._seq_elts(out_shape) or (
            [out_shape] if isinstance(out_shape, ast.Call) else []
        )
        dtypes = []
        for e in elts:
            if isinstance(e, ast.Call) and len(e.args) > 1:
                dt = dotted_name(e.args[1])
                if dt:
                    dtypes.append(dt)
        return dtypes

    def _weak_via_project(self, expr, kmodule, depth=0) -> bool:
        """Weak-typed float constant reachable only through the symbol
        table: an imported name resolving to a float literal / ±inf."""
        if depth > 4:
            return False
        if isinstance(expr, ast.UnaryOp) and isinstance(
            expr.op, (ast.USub, ast.UAdd)
        ):
            return self._weak_via_project(expr.operand, kmodule, depth)
        dn = dotted_name(expr)
        if not dn:
            return False
        # same-module literals and attributes are dtype-x64/GL303's
        # domain; only cross-module resolution is this pass's finding
        if dn in kmodule.constants or dn in _INF_ATTRS:
            return False
        resolved = self.project.resolve_constant(kmodule, dn)
        if resolved is None:
            return False
        return self._weak_expr(resolved, depth + 1)

    def _weak_expr(self, expr, depth=0) -> bool:
        if depth > 4:
            return False
        if isinstance(expr, ast.Constant):
            return isinstance(expr.value, float)
        if isinstance(expr, ast.UnaryOp) and isinstance(
            expr.op, (ast.USub, ast.UAdd)
        ):
            return self._weak_expr(expr.operand, depth)
        return dotted_name(expr) in _INF_ATTRS

    def _check_fills(self, kfunc, kmodule, out_dtypes: List[str]):
        dtype_note = (
            f" (out_shape declares {', '.join(sorted(set(out_dtypes)))})"
            if out_dtypes
            else ""
        )
        kctx = kmodule.ctx
        for sub in ast.walk(kfunc):
            if not isinstance(sub, ast.Call):
                continue
            canon = self.project.canonical(kmodule, call_name(sub))
            if canon in _WHERE:
                branches = sub.args[1:3]
            elif canon in _FULL:
                branches = sub.args[1:2]
            else:
                continue
            for b in branches:
                if not self._weak_via_project(b, kmodule):
                    continue
                if (id(sub), "GL705") in self._seen:
                    continue
                self._seen.add((id(sub), "GL705"))
                self.report(
                    kctx, sub, "GL705",
                    f"weak-typed fill constant {dotted_name(b) or '?'} "
                    "(resolved through an import) in a pallas kernel: "
                    "under x64 the fill promotes the select to f64 and "
                    f"breaks the out_shape dtype contract{dtype_note} — "
                    "materialize at the ref dtype "
                    "(jnp.asarray(c, dtype=ref.dtype) / full_like)",
                )
                break
