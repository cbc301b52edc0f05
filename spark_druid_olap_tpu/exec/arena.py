"""Segment-stacked resident arena: one-dispatch execution (ISSUE 14).

The receipts say the warm single-query floor is the dispatch loop: one
host->device program launch per segment batch, O(segments/unroll) round
trips per query even when every column is already resident.  The partial
-aggregate fold composes freely (arXiv:2603.26698's merge-tree algebra),
so the entire in-scope fold can run as ONE traced program — this module
is that program.

* **Stacked layout** — in-scope segments of EQUAL padded row count stack
  into one device-resident array per column (plus the stacked validity
  masks: each segment's existing ROW_PAD tail is the padding, so the
  stack adds zero pad waste), `[B, R / 128, 128]`: a TPU tiles an
  array's two minor dimensions in (8, 128), so a `[B, R]` stack lays a
  block's row as one sublane of every tile and the scan's slice of it is
  a strided gather (on a v5e 23 us to copy a 2 MB value row out and 30
  for the group-id fusion to read its keys, against 3.5 and 4.2 from
  this layout, where a block is one contiguous run that the scan body
  flattens back to `[R]` for nothing; PERF.md, PR 36).  The stack is placed
  through `Engine._put_device_col` under an `(("arena", *uids), ...)`
  key, so the residency byte budget, LRU eviction, h2d fault site, link
  accounting, and prefetch poisoning all hold unchanged.
* **One traced program** — `lax.scan` over the segment blocks with the
  partial fold INSIDE the trace.  The scan carry replicates the dispatch
  loop's exact fold tree (per-batch in-trace left fold, then cross-batch
  fold in canonical batch order) via boundary flags and live-flag
  selects, so results are BYTE-identical to the loop path: f32 partial
  sums are not reassociation-safe, and `jnp.where` is an exact bitwise
  select.
* **One device call** (ISSUE 33) — the zero carry is made and the last
  batch flushed INSIDE the trace (`_member_init` before the scan,
  `finish_member` after it), the batch-start flags are resident beside
  the stack and an all-true membership is a trace constant: a warm
  request that covers its plan in one chunk enqueues the scanned program
  and nothing else between `arena_build` and the caller's fetch, as the
  mesh's program always has.  The chunked form threads the carry through
  per-batch calls (made by the first, flushed by the last) and donates
  it on backends that support aliasing (TPU/GPU), so it never holds two
  copies of the `[G, M]` state.
* **Shape discipline** — `partial_aggregate`'s row-block partitioning
  depends on the segment's padded row count, so stacking UNEQUAL shapes
  to a common max would change the fold tree and break byte identity.
  The arena therefore covers the longest PREFIX of whole dispatch
  batches whose segments share one shape (the common case: uniform
  historicals, then a short tail / delta suffix); the remainder runs
  through the existing loop path and the cross-batch fold continues in
  canonical order.  Sketch aggregations (no exact in-carry identities)
  and sparse/adaptive routes decline the arena entirely.
* **Anytime answers** — with a deadline or partial collector armed the
  scan dispatches in per-batch chunks, carry threaded through, with
  `checkpoint_partial` between chunks: truncation lands exactly on the
  loop path's batch boundaries, so the coverage contract (seen segments
  / rows) is unchanged.
* **Fusion** — a fused micro-batch executes against ONE arena: members
  share the stacked columns and the scan computes every member's fold in
  the same dispatch, with per-block membership flags as DATA (not trace
  constants — one compiled program serves any member->segment mapping of
  the same shape).
* **Unified executor core** — `parallel/spmd_arena.py` shard_maps this
  exact fold (`_member_init` / `_fold_block` / `finish_member`) over a
  device-major permutation of the same stacked layout, with a psum/pmin/
  pmax boundary merge, so the single-device and mesh paths lower the ONE
  program; changes to the fold semantics here propagate to the SPMD path
  by construction.
"""

from __future__ import annotations

import contextlib
import contextvars
from typing import Any, Dict, List, Optional, Sequence, Tuple

import numpy as np

from ..obs import (
    SCOPE_ARENA_SCAN,
    SCOPE_CARRY_MERGE,
    SPAN_ARENA_BUILD,
    SPAN_SEGMENT_DISPATCH,
    device_scope,
    prof,
    span,
)
from ..resilience import checkpoint_partial, current_deadline, fire
from ..utils.log import get_logger

log = get_logger("exec.arena")

# the arena pins every covered batch's columns resident SIMULTANEOUSLY
# (the loop path pages batches through the LRU window); cap coverage at
# this fraction of the residency byte budget so one query cannot evict
# the whole working set behind itself
ARENA_BUDGET_FRACTION = 0.5

# kernel strategies whose per-segment partial program is shape-uniform
# and scannable.  sparse/adaptive never reach here (they route before
# the dense partials path); anything unrecognized declines to the loop.
_SCANNABLE = frozenset({"dense", "scatter", "pallas"})

# per-query opt-out (SessionConfig.arena_execution is the session-wide
# gate; this contextvar scopes a single execution)
_disabled: contextvars.ContextVar[bool] = contextvars.ContextVar(
    "arena_disabled", default=False
)


@contextlib.contextmanager
def arena_disabled():
    """Opt the enclosed executions out of the arena (per-query escape
    hatch: the dispatch loop is the reference path; the counterfactual
    bench and the byte-identity tests run both sides through this)."""
    tok = _disabled.set(True)
    try:
        yield
    finally:
        _disabled.reset(tok)


def query_disabled() -> bool:
    return _disabled.get()


def arena_key(uids: Sequence, kind: str, name: Optional[str] = None):
    """Residency-cache key of one stacked arena buffer.  The leading
    element is the `("arena", *uids)` tuple — never a bare segment uid,
    so `Engine.evict_segments`' per-segment pops cannot alias it, and
    the arena-slice invalidation scan can intersect on the uid tail."""
    head = ("arena",) + tuple(uids)
    return (head, kind, name) if name is not None else (head, kind)


def is_arena_key(key) -> bool:
    return (
        isinstance(key, tuple)
        and len(key) >= 2
        and isinstance(key[0], tuple)
        and len(key[0]) >= 1
        and key[0][0] == "arena"
    )


class ArenaPlan:
    """One query scope's arena coverage: which whole dispatch batches
    stack (uniform shape, within the byte-budget fraction), the per-block
    batch-start flags that replicate the loop path's fold tree, and the
    remainder batches the loop path still owns."""

    __slots__ = (
        "segs", "uids", "batches", "start", "remainder", "rows", "nbytes",
        "folded",
    )

    def __init__(self, batches_covered, remainder, nbytes):
        self.batches = [list(b) for b in batches_covered]
        self.segs = [s for b in self.batches for s in b]
        self.uids = tuple(s.uid for s in self.segs)
        start = np.zeros(len(self.segs), dtype=bool)
        pos = 0
        for b in self.batches:
            start[pos] = True
            pos += len(b)
        self.start = start
        self.remainder = list(remainder)
        self.rows = sum(s.num_rows for s in self.segs)
        self.nbytes = int(nbytes)
        # covered batches actually folded so far (run_plan updates it
        # per chunk): the caller's fallback/truncation decisions key off
        # whether any state exists yet
        self.folded = 0


def plan_for(engine, batches, names) -> Optional[ArenaPlan]:
    """Coverage decision for one scope's dispatch batches, or None when
    the arena cannot beat the loop (fewer than two coverable batches:
    the loop path is already one dispatch, and stacking would only add
    a host copy)."""
    batches = [list(b) for b in batches]
    if len(batches) < 2:
        return None
    shape0 = batches[0][0].num_rows_padded
    budget = int(
        engine._device_cache.budget_bytes * ARENA_BUDGET_FRACTION
    )
    covered: List[List] = []
    nbytes = 0
    for b in batches:
        if any(s.num_rows_padded != shape0 for s in b):
            break
        est = sum(
            int(s.valid.nbytes)
            + sum(int(s.column(n).nbytes) for n in names)
            for s in b
        )
        if covered and nbytes + est > budget:
            break
        covered.append(b)
        nbytes += est
    if len(covered) < 2:
        return None
    return ArenaPlan(covered, batches[len(covered):], nbytes)


def stacked_cols(engine, ds, plan: ArenaPlan, names):
    """Fetch (or build and place) the plan's stacked `[B, R / 128, 128]`
    columns and its `[B]` batch-start flags: `(cols, start)`.

    Every placement goes through `Engine._put_device_col` (transfer-
    discipline GL19xx): residency accounting, the byte-budget LRU, the
    h2d fault site, and link attribution all see the stack exactly like
    any segment column.  Retired-uid poisoning is handled upstream —
    `Engine.evict_segments` drops intersecting arena slices, and a plan
    is built from a consistent datasource snapshot.  The flags are a
    function of the plan's uids and batch lengths, so they are placed
    once beside the stack and a warm request only looks them up."""
    cols: Dict[str, Any] = {}

    def stack(rows):
        # block-contiguous (module docstring); R is a ROW_PAD multiple
        return np.stack(rows).reshape(len(rows), -1, 128)

    def lookup(key, host_fn):
        arr = engine._device_cache.get(key)
        if arr is not None:
            prof.note_residency(hit=True)
            return arr
        exc = engine._pipeline.take_poison(key)
        if exc is not None:
            raise exc
        prof.note_residency(hit=False)
        return engine._put_device_col(key, host_fn(), ds.name)

    for n in names:
        cols[n] = lookup(
            arena_key(plan.uids, "col", n),
            lambda n=n: stack(
                [np.asarray(s.column(n)) for s in plan.segs]
            ),
        )
    cols["__valid"] = lookup(
        arena_key(plan.uids, "valid"),
        lambda: stack([np.asarray(s.valid) for s in plan.segs]),
    )
    if ds.time_column and ds.time_column in cols:
        cols["__time"] = cols[ds.time_column]
    # the batch lengths are in the key: the byte budget can cut the same
    # uids into other batches for a wider column set
    start = lookup(
        arena_key(
            plan.uids, "start", tuple(len(b) for b in plan.batches)
        ),
        lambda: plan.start,
    )
    return cols, start


# ---------------------------------------------------------------------------
# the one traced program
# ---------------------------------------------------------------------------


def _donate_carry() -> bool:
    """Donate the fold-state carry across chunk dispatches?  Buffer
    aliasing is implemented on TPU/GPU; the CPU backend ignores the
    request with a warning per compile, so stay quiet there."""
    import jax

    return jax.default_backend() != "cpu"


def _select(flag, a, b):
    """Exact bitwise per-leaf select (jnp.where never reassociates)."""
    import jax.numpy as jnp

    return jnp.where(flag, a, b)


def _member_init(lowering):
    """Zero-seeded carry for one member: (total, batch) x (sums, mins,
    maxs, live).  Values behind a False live flag are dead by
    construction (every read is select-guarded), so zeros are safe —
    no -0.0 / identity-element hazards can reach a live lane."""
    import jax.numpy as jnp

    la, G = lowering.la, lowering.num_groups

    def zeros():
        return (
            jnp.zeros((G, len(la.sum_names)), jnp.float32),
            jnp.zeros((G, len(la.min_names)), jnp.float32),
            jnp.zeros((G, len(la.max_names)), jnp.float32),
            jnp.asarray(False),
        )

    # two SEPARATE sets of buffers: the carry is donated on TPU/GPU, and
    # one buffer appearing twice in a donated argument is refused there
    # (`Attempt to donate the same buffer twice`)
    return zeros() + zeros()  # (t_s, t_mn, t_mx, t_live, b_s, b_mn, b_mx, b_live)


def _fold_block(carry_i, block_state, start_b, memb_b):
    """One member's carry update for one segment block — the loop path's
    exact fold tree, replayed with live-flag selects:

      * at a batch START, the accumulated batch state flushes into the
        total (the loop path's host-side cross-batch fold), but only if
        the member accumulated anything in that batch (the fused loop's
        None-skip);
      * then the block's partial folds into the (possibly fresh) batch
        accumulator, gated on the member's block membership."""
    import jax.numpy as jnp

    t_s, t_mn, t_mx, t_live, b_s, b_mn, b_mx, b_live = carry_i
    s, mn, mx = block_state
    flush = jnp.logical_and(start_b, b_live)
    t_s = _select(flush, _select(t_live, t_s + b_s, b_s), t_s)
    t_mn = _select(flush, _select(t_live, jnp.minimum(t_mn, b_mn), b_mn), t_mn)
    t_mx = _select(flush, _select(t_live, jnp.maximum(t_mx, b_mx), b_mx), t_mx)
    t_live = jnp.logical_or(t_live, flush)
    b_live = jnp.logical_and(b_live, jnp.logical_not(start_b))
    b_s2 = _select(memb_b, _select(b_live, b_s + s, s), b_s)
    b_mn2 = _select(
        memb_b, _select(b_live, jnp.minimum(b_mn, mn), mn), b_mn
    )
    b_mx2 = _select(
        memb_b, _select(b_live, jnp.maximum(b_mx, mx), mx), b_mx
    )
    b_live = jnp.logical_or(b_live, memb_b)
    return (t_s, t_mn, t_mx, t_live, b_s2, b_mn2, b_mx2, b_live)


def build_arena_program(lowerings, strategies, share=None):
    """The ONE traced scan over stacked segment blocks, computing every
    member's partial fold in a single dispatch.  Signature:

        fn(carry, cols, start, memb, init=False, finish=False)

    `cols` maps column name -> [Bc, R / 128, 128] (`stacked_cols`; a
    [Bc, R] stack is taken as well); `start` is the [Bc] batch-start
    flag vector; `memb` is [Bc, n_members] block membership, or None for
    all-true (written in the trace).  Flags are DATA, not trace
    constants: one compiled program (per chunk shape) serves any
    membership pattern.  `init` and `finish` are STATIC: with `init` the
    trace makes the zero carry itself (`carry` is None), with `finish`
    it flushes the carry and returns `(sums, mins, maxs, live)` a member
    — so a call that covers the whole plan, `fn(None, cols, start, memb,
    init=True, finish=True)`, is the request's one device computation.
    Chunking threads the carry through repeated calls, `init` on the
    first and `finish` on the last — the op sequence (hence byte
    identity) is invariant to where the chunk boundaries fall.  Each
    form compiles on its first use."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    from .engine import _segment_partials

    n = len(lowerings)

    def fn(carry, cols, start, memb, init=False, finish=False):
        def body(c, xs):
            cols_b, start_b, memb_b = xs
            # a block back to its rows: the same words in the same order
            cols_b = {k: a.reshape(-1) for k, a in cols_b.items()}
            memo: Dict[Any, Any] = {}
            out = []
            for i in range(n):
                s, mn, mx, _sk = _segment_partials(
                    lowerings[i],
                    strategies[i],
                    dict(cols_b),
                    memo=memo if share is not None else None,
                    share=share[i] + (0,) if share is not None else None,
                )
                with device_scope(SCOPE_CARRY_MERGE):
                    out.append(
                        _fold_block(
                            c[i], (s, mn, mx), start_b, memb_b[i]
                        )
                    )
            return tuple(out), None

        if init:
            carry = tuple(_member_init(lw) for lw in lowerings)
        if memb is None:
            memb = jnp.ones((start.shape[0], n), dtype=bool)
        with device_scope(SCOPE_ARENA_SCAN):
            c2, _ = lax.scan(body, carry, (cols, start, memb))
        if finish:
            with device_scope(SCOPE_CARRY_MERGE):
                return tuple(finish_member(c) for c in c2)
        return c2

    # pure builder: every caller (Engine._arena_program /
    # _arena_fused_program) stores the result in the engine program
    # cache under a structured query key
    # (a flushing chunk returns four of a member's eight donated buffers:
    # JAX warns once a compile that the batch half "were not usable")
    donate = {"donate_argnums": (0,)} if _donate_carry() else {}
    # graftlint: disable=jit-cache -- caller caches under a query key
    return jax.jit(fn, static_argnames=("init", "finish"), **donate)


def finish_member(carry_i):
    """Final batch->total flush of one member's carry (the loop path's
    last host-side fold).  Returns (sums, mins, maxs, live) — `live` is
    False when the member touched no block (empty scope: the caller
    substitutes `PartialState.empty`, exactly like the loop path)."""
    import jax.numpy as jnp

    t_s, t_mn, t_mx, t_live, b_s, b_mn, b_mx, b_live = carry_i
    s = _select(b_live, _select(t_live, t_s + b_s, b_s), t_s)
    mn = _select(
        b_live, _select(t_live, jnp.minimum(t_mn, b_mn), b_mn), t_mn
    )
    mx = _select(
        b_live, _select(t_live, jnp.maximum(t_mx, b_mx), b_mx), t_mx
    )
    return s, mn, mx, jnp.logical_or(t_live, b_live)


def _site_armed(site: str) -> bool:
    """Is fault injection armed at `site`?  Lock-free when the injector
    singleton was never constructed (the production fast path)."""
    if not site:
        return False
    from .. import resilience as _res

    inj = _res._injector
    return inj is not None and inj.armed(site)


def _chunk_bounds(plan: ArenaPlan, site: str = "") -> List[Tuple[int, int, int]]:
    """(block_lo, block_hi, batch_index) per dispatch chunk.  One chunk
    per BATCH when a wall-clock deadline is armed — or fault injection
    targets the checkpoint site — so truncation lands exactly on the
    loop path's batch boundaries, keeping the anytime-answer coverage
    contract.  One chunk for the whole plan otherwise — the O(1)
    -dispatch fast path.  A partial collector ALONE does not chunk: the
    served default arms one on every query, but without a deadline the
    loop path's checkpoints never truncate either, so the single-chunk
    scan honors the same contract for free."""
    if current_deadline() is None and not _site_armed(site):
        return [(0, len(plan.segs), len(plan.batches) - 1)]
    out = []
    pos = 0
    for bi, b in enumerate(plan.batches):
        out.append((pos, pos + len(b), bi))
        pos += len(b)
    return out


def run_plan(
    engine, ds, plan: ArenaPlan, names, program,
    memb: Optional[np.ndarray] = None, pc=None, checkpoint_site="",
    single_chunk: bool = False, span_attrs: Optional[dict] = None,
):
    """Build/fetch the stacked columns, then dispatch the scan program
    over the plan's chunks.  Returns (states, batches_folded): the
    finished `(sums, mins, maxs, live)` of every member, flushed inside
    the program, plus how many covered batches actually folded (fewer
    than planned on a deadline/partial truncation; `states` is None
    when none did).

    One chunk is the WHOLE form: the request's single device call makes
    its own zero carry and flushes it, and nothing else is enqueued
    between `arena_build` and the caller's fetch.  Several chunks (a
    deadline armed, `_chunk_bounds`) thread the carry through the CHUNK
    form's calls.  The stack build lives under the `arena_build` receipt
    bucket; each chunk dispatch is a `segment_dispatch` span (attr
    `form`, and `span_attrs`, the caller's word on what the dispatch
    is), so `dispatch_count` and the device/transfer attribution stay
    honest."""
    import time as _time

    from .engine import _row_counts

    # the FIRST chunk's deadline checkpoint runs before the stack build
    # (the chunk-0 check in the loop below is skipped): an already-gone
    # deadline skips the H2D work entirely and hands the caller zero
    # folded batches.  Hoisting (not adding) the call keeps the site's
    # call count identical to the loop path's one-per-batch cadence, so
    # skip=K fault injection truncates both paths at the same boundary.
    if checkpoint_site and checkpoint_partial(checkpoint_site):
        return None, 0
    with span(
        SPAN_ARENA_BUILD, blocks=len(plan.segs), batches=len(plan.batches),
    ):
        cols, start = stacked_cols(engine, ds, plan, names)
    # the fused path forces one chunk: its deadline contract is checked
    # once up front by the caller and an expiry re-routes members to
    # their serial partial-capable paths — no mid-scan truncation
    chunks = (
        [(0, len(plan.segs), len(plan.batches) - 1)]
        if single_chunk
        else _chunk_bounds(plan, checkpoint_site)
    )
    whole = len(chunks) == 1
    carries = None
    done = 0
    for ci, (lo, hi, last_bi) in enumerate(chunks):
        # ci == 0 was checkpointed above, before the build
        if ci and checkpoint_site and checkpoint_partial(checkpoint_site):
            break
        # the same fault-injection site every loop-path dispatch fires:
        # an injected (or real pre-dispatch) transient fault walks the
        # retry/breaker machinery whether or not the arena is on
        fire("device_dispatch")
        m = engine._m
        with span(
            SPAN_SEGMENT_DISPATCH,
            arena=hi - lo,
            chunk=f"{ci + 1}/{len(chunks)}",
            form="whole" if whole else "chunk",
            **(span_attrs or {}),
        ):
            if whole:
                xs = (cols, start, memb)
            else:
                # a chunk's slice of every stacked column and flag is an
                # eager device operation each: part of its launch
                xs = (
                    {n: a[lo:hi] for n, a in cols.items()},
                    start[lo:hi],
                    None if memb is None else memb[lo:hi],
                )
            # first call of a newly-built program = trace+compile:
            # attribute it exactly like _call_segment_program does
            t0 = (
                _time.perf_counter()
                if ci == 0
                and m is not None
                and not m.program_cache_hit
                and m.compile_ms == 0
                else None
            )
            t_call = _time.perf_counter()
            carries = program(
                carries, *xs, init=ci == 0, finish=ci == len(chunks) - 1
            )
            carries = prof.dispatch_sync(carries, t_call)
            if t0 is not None:
                m.compile_ms = (_time.perf_counter() - t0) * 1e3
                prof.note_compile(m.compile_ms)
        if pc is not None:
            for bi in range(done, last_bi + 1):
                b = plan.batches[bi]
                pc.add_seen(len(b), *_row_counts(b))
        done = last_bi + 1
        plan.folded = done
    if done < len(plan.batches):
        # truncated before the chunk that flushes: flush what folded
        carries = tuple(finish_member(c) for c in carries)
    return carries, done
