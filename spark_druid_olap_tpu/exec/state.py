"""The partial-aggregate state and its merge (⊕).

Every group-by executor — the batch loops, the fused micro-batch, the
progressive and streaming loops, the adaptive tier, the mesh, the result
cache and the cluster broker — produces, merges and renders ONE state:

    PartialState(sums f32[G, S], mins f32[G, N], maxs f32[G, X],
                 sketches {agg name: state})

`sums` holds `LoweredAggs.sum_names` (row counts first), `mins` and
`maxs` their names; a sketch state is an HLL agg's registers
int32[G, 2^p] (or, once `estimable` made it, their histogram
int32[G, 34 - p]), a quantiles agg's int32[G, K + 1, 2], a theta agg's
uint32[G, K].  The device and host forms are the same type: jnp leaves
on the device (or tracers inside a program), numpy leaves on the host
(`to_host`).  A NamedTuple is a JAX pytree, so a program takes and
returns it as it is.

`merge` is the one ⊕: sums add, mins and maxs fold, sketches merge by
type; `PartialState.empty` is its identity.  The sparse tier's slot
state (`gids`, `overflow`) is another state and stays out of this one.
"""

from __future__ import annotations

from typing import Any, Dict, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from ..models import aggregations as A
from ..obs import (
    SCOPE_SKETCH_MERGE,
    SPAN_SEGMENT_DISPATCH,
    device_scope,
    span,
)
from ..ops import hll as hll_ops
from ..ops import quantiles as quantiles_ops
from ..ops import theta as theta_ops


def is_hll(agg) -> bool:
    return isinstance(agg, (A.HyperUnique, A.CardinalityAgg))


class PartialState(NamedTuple):
    sums: Any
    mins: Any
    maxs: Any
    sketches: Dict[str, Any]

    @staticmethod
    def empty(la, G: int) -> "PartialState":
        """The zero-row state over `la`'s aggregations and G groups, on
        the device: the identity of `merge`."""
        sketches: Dict[str, Any] = {}
        for agg in la.sketch_aggs:
            if is_hll(agg):
                sketches[agg.name] = jnp.zeros(
                    (G, 1 << agg.precision), jnp.int32
                )
            elif isinstance(agg, A.QuantilesSketch):
                # [G, K+1, 2]: K empty sample slots + the zero N-counter row
                pr = jnp.full(
                    (G, agg.size), quantiles_ops.SENTINEL_P, jnp.int32
                )
                vb = jnp.zeros((G, agg.size), jnp.int32)
                sample = jnp.stack([pr, vb], axis=-1)
                extra = jnp.zeros((G, 1, 2), jnp.int32)
                sketches[agg.name] = jnp.concatenate([sample, extra], axis=1)
            else:
                sketches[agg.name] = jnp.full(
                    (G, agg.size), theta_ops.SENTINEL, jnp.uint32
                )
        return PartialState(
            jnp.zeros((G, len(la.sum_names)), jnp.float32),
            jnp.full((G, len(la.min_names)), jnp.inf, jnp.float32),
            jnp.full((G, len(la.max_names)), -jnp.inf, jnp.float32),
            sketches,
        )


def merge(la, acc, new: PartialState) -> PartialState:
    """acc ⊕ new, in that operand order (f32 sums are not
    reassociation-safe: a fold keeps its bytes only in one order);
    `new` where `acc` is None.  Inside a trace, on device arrays or on
    host (numpy) states.  An aggregation `new` lacks keeps `acc`'s
    state: a batch program folds a theta aggregation's rows apart
    (engine `_batch_partials`)."""
    if acc is None:
        return new
    host = isinstance(new.sums, np.ndarray)
    xp = np if host else jnp
    return PartialState(
        acc.sums + new.sums,
        xp.minimum(acc.mins, new.mins),
        xp.maximum(acc.maxs, new.maxs),
        _merge_sketches(la, acc.sketches, new.sketches, host),
    )


def _merge_sketches(la, acc, new, host):
    if not la.sketch_aggs:
        return acc
    xp = np if host else jnp
    out = dict(acc)
    with device_scope(SCOPE_SKETCH_MERGE):
        for agg in la.sketch_aggs:
            st = new.get(agg.name)
            if st is None:
                continue
            prev = out.get(agg.name)
            if prev is None:
                out[agg.name] = st
            elif is_hll(agg):
                out[agg.name] = xp.maximum(prev, st)
            elif isinstance(agg, A.QuantilesSketch):
                out[agg.name] = quantiles_ops.merge_states(prev, st, agg.size)
            else:
                out[agg.name] = theta_ops.merge_states(prev, st, agg.size)
    if host:  # the sorted unions run as jnp: their results come back
        out = {k: np.asarray(v) for k, v in out.items()}
    return out


def to_host(tree):
    """A state, or a tree of states, in its host form: ONE
    `jax.device_get` of every leaf (numpy leaves pass as they are)."""
    return jax.device_get(tree)


def with_histograms(la, state: PartialState) -> PartialState:
    """`state` with each HLL agg's registers replaced by their register
    histogram (`ops/hll.register_histogram`), eagerly or inside a trace."""
    if not any(is_hll(a) for a in la.sketch_aggs):
        return state
    sketches = dict(state.sketches)
    for agg in la.sketch_aggs:
        if is_hll(agg):
            sketches[agg.name] = hll_ops.register_histogram(
                sketches[agg.name], agg.precision
            )
    return state._replace(sketches=sketches)


def estimable(la, state: PartialState) -> PartialState:
    """What of a device state is fetched for `finalize_groupby`: each HLL
    agg's merged registers int32[G, 2^p] become their register histogram
    int32[G, 34 - p] on the device, so the host fetches and estimates
    from 34 - p integers a group, not 2^p.  Other leaves pass as they
    are.  A caller whose state must survive as registers (a captured
    state, one handed to a merger) does not call this.  The launch is a
    `segment_dispatch` span (attr `sketch`): it counts as a dispatch."""
    if not any(is_hll(a) for a in la.sketch_aggs):
        return state
    with span(SPAN_SEGMENT_DISPATCH, sketch="histogram"):
        return with_histograms(la, state)


def sketch_nbytes(state: PartialState) -> int:
    """Bytes of a fetched state's sketches (`QueryMetrics.
    sketch_state_bytes`)."""
    return sum(int(np.asarray(v).nbytes) for v in state.sketches.values())


def nbytes(state: PartialState) -> int:
    """Bytes of a host state, every leaf."""
    return sum(int(x.nbytes) for x in jax.tree.leaves(state))
