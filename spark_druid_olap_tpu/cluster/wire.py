"""Cluster wire codec: the host partial-state currency over HTTP.

The broker/historical RPC moves the host `PartialState` (exec/state.py)
as one JSON document, `{"sums", "mins", "maxs", "sketches": {name: ...}}`,
each array as its dtype + shape + base64 payload — because
the historical surface is the existing stdlib HTTP server and JSON is
its wire format.  Decode is STRICT: a torn body (the
`cluster.torn_response` fault site truncates mid-payload), a missing
key, or a byte count that disagrees with dtype x shape raises
`WireDecodeError`, which the broker's scatter loop treats as a replica
failure and fails over — a corrupt replica answer must never ⊕ into
the merge.

The OBSERVABILITY side-channel (ISSUE 19) rides the same responses
with the OPPOSITE decode posture: `encode_trace`/`decode_trace` move a
historical's rendered span subtree next to its partial state, and any
problem with that payload — torn, oversized, wrong shape — degrades to
an `untraced` stub, NEVER a replica failure.  A query must not fail
over (or lose a good partial state) because its telemetry was ugly.
`trace_headers` builds the propagation headers the broker attaches to
every scatter RPC (`X-Druid-Query-Id` — Druid's own echo header — plus
`X-Sdol-Parent-Span`, the OTLP span id of the broker's `cluster_rpc`
span) so both processes trace under one identity.
"""

from __future__ import annotations

import base64
import json
from typing import Dict, Optional

import numpy as np

from ..exec.state import PartialState

__all__ = [
    "WireDecodeError",
    "encode_state",
    "decode_state",
    "HEADER_QUERY_ID",
    "HEADER_PARENT_SPAN",
    "TRACE_MAX_BYTES",
    "trace_headers",
    "encode_trace",
    "decode_trace",
    "untraced_stub",
]

_STATE_KEYS = ("sums", "mins", "maxs")

# trace-propagation headers (graftlint GL2701: every cluster RPC sender
# must attach these — through `trace_headers`, so the names live here)
HEADER_QUERY_ID = "X-Druid-Query-Id"
HEADER_PARENT_SPAN = "X-Sdol-Parent-Span"

# upper bound for one rendered span subtree on the wire, each way: an
# instrumentation explosion (a scan that opened a span per row) must not
# bloat every scatter response — past the cap the subtree degrades to an
# `untraced` stub while the partial state ships untouched
TRACE_MAX_BYTES = 262_144


class WireDecodeError(ValueError):
    """A replica response that cannot be decoded into a valid partial
    state (torn payload, missing key, shape/byte mismatch)."""


def _encode_array(a: np.ndarray) -> dict:
    a = np.ascontiguousarray(a)
    return {
        "dtype": str(a.dtype),
        "shape": list(a.shape),
        "data": base64.b64encode(a.tobytes()).decode("ascii"),
    }


def _decode_array(doc) -> np.ndarray:
    if not isinstance(doc, dict):
        raise WireDecodeError(f"array doc is {type(doc).__name__}, not dict")
    try:
        dtype = np.dtype(doc["dtype"])
        shape = tuple(int(x) for x in doc["shape"])
        raw = base64.b64decode(str(doc["data"]).encode("ascii"),
                               validate=True)
    except WireDecodeError:
        raise
    except Exception as e:
        raise WireDecodeError(f"malformed array doc: {e}") from e
    want = dtype.itemsize * int(np.prod(shape, dtype=np.int64))
    if len(raw) != want:
        raise WireDecodeError(
            f"torn array payload: {len(raw)} bytes for "
            f"{dtype}{list(shape)} (want {want})"
        )
    # copy: frombuffer views are read-only and the merge fold must own
    # writable arrays
    return np.frombuffer(raw, dtype=dtype).reshape(shape).copy()


def encode_state(state: PartialState) -> dict:
    """Host partial state -> JSON-safe document."""
    doc = {k: _encode_array(getattr(state, k)) for k in _STATE_KEYS}
    doc["sketches"] = {
        str(name): _encode_array(arr) for name, arr in state.sketches.items()
    }
    return doc


def decode_state(doc) -> PartialState:
    """JSON document -> host partial state (strict; raises
    `WireDecodeError` on anything short of a complete valid state)."""
    if not isinstance(doc, dict):
        raise WireDecodeError(
            f"state doc is {type(doc).__name__}, not dict"
        )
    missing = [k for k in _STATE_KEYS if k not in doc]
    if missing:
        raise WireDecodeError(f"state doc missing keys {missing}")
    sums, mins, maxs = (_decode_array(doc[k]) for k in _STATE_KEYS)
    sk = doc.get("sketches")
    if sk is not None and not isinstance(sk, dict):
        raise WireDecodeError("sketches member is not a dict")
    return PartialState(sums, mins, maxs, {
        str(name): _decode_array(arr) for name, arr in (sk or {}).items()
    })


# ---------------------------------------------------------------------------
# Trace side-channel (ISSUE 19): lenient by design — degrade, never fail
# ---------------------------------------------------------------------------


def trace_headers(query_id: str, parent_span_id: str = "") -> Dict[str, str]:
    """The propagation headers a cluster RPC sender attaches (GL2701):
    the query id both processes trace under, plus the broker-side span
    id the historical's trace records as its cross-process parent."""
    headers = {HEADER_QUERY_ID: str(query_id or "")}
    if parent_span_id:
        headers[HEADER_PARENT_SPAN] = str(parent_span_id)
    return headers


def untraced_stub(node: str, reason: str) -> dict:
    """The degraded graft: a zero-duration marker node standing where a
    historical's subtree would have been.  Shape-compatible with a
    rendered span node so the grafted tree stays well-formed; `attrs`
    name the node and why its telemetry is missing."""
    return {
        "name": "query",
        "start_ms": 0.0,
        "duration_ms": 0.0,
        "attrs": {
            "node": str(node or "?"),
            "remote": True,
            "untraced": True,
            "reason": str(reason or "unknown"),
        },
    }


def _valid_span_node(node, depth: int = 0) -> bool:
    """Structural check over a rendered span node: dict shape, string
    name, numeric timings, recursively valid children.  Bounded depth so
    a hostile/corrupt payload cannot recurse past sys limits."""
    if depth > 64 or not isinstance(node, dict):
        return False
    if not isinstance(node.get("name"), str):
        return False
    for key in ("start_ms", "duration_ms"):
        if not isinstance(node.get(key, 0.0), (int, float)):
            return False
    attrs = node.get("attrs")
    if attrs is not None and not isinstance(attrs, dict):
        return False
    children = node.get("children")
    if children is None:
        return True
    if not isinstance(children, list):
        return False
    return all(_valid_span_node(c, depth + 1) for c in children)


def encode_trace(
    trace_doc: Optional[dict], max_bytes: int = TRACE_MAX_BYTES
) -> Optional[dict]:
    """Historical side: the rendered subtree of `QueryTrace.to_dict()`
    ready to ride the partial response, or an `untraced` stub when it is
    malformed or oversized.  Never raises and never returns something
    that would fail the response encode."""
    if not isinstance(trace_doc, dict):
        return None
    node = trace_doc.get("spans")
    if not _valid_span_node(node):
        return untraced_stub("", "malformed local trace")
    subtree = dict(node)
    # the remote receipt rides INSIDE the graft root so receipt folding
    # and obs_dump see per-node attribution even from the subtree alone
    receipt = trace_doc.get("receipt")
    if isinstance(receipt, dict):
        subtree["receipt"] = receipt
    try:
        if len(json.dumps(subtree)) > max(1024, int(max_bytes)):
            return untraced_stub("", "trace payload over size cap")
    except (TypeError, ValueError):
        return untraced_stub("", "unserializable trace payload")
    return subtree


def decode_trace(
    doc, node: str, max_bytes: int = TRACE_MAX_BYTES
) -> dict:
    """Broker side: validate a replica's trace payload into a graftable
    subtree.  ANY defect — absent, torn, wrong shape, oversized —
    returns an `untraced` stub for `node`; this function never raises
    (trace trouble must not fail a replica that computed a good
    partial)."""
    if doc is None:
        return untraced_stub(node, "replica returned no trace")
    try:
        if not _valid_span_node(doc):
            return untraced_stub(node, "malformed trace payload")
        if len(json.dumps(doc)) > max(1024, int(max_bytes)):
            return untraced_stub(node, "trace payload over size cap")
    except Exception:
        return untraced_stub(node, "undecodable trace payload")
    out = dict(doc)
    attrs = dict(out.get("attrs") or {})
    attrs.setdefault("node", str(node or "?"))
    attrs["remote"] = True
    out["attrs"] = attrs
    return out
