"""Historical process: a read-only serving replica over the shared
snapshot store (cluster/, ISSUE 16).

One historical = one `TPUOlapContext` mmap-booted from the SAME
`storage_dir` the broker writes (snapshot load reads .npy headers only
— boot is metadata-time, ~57 ms at SF10 — and the pages of a segment
fault in lazily as queries touch it, so a node effectively loads only
its ASSIGNED subset) + one `OlapServer` exposing the existing wire
surface, including `POST /druid/v2/cluster/partial`.

Historicals are deliberately read-only consumers of the store: fsync
off, no flush sweep, no compaction — the broker owns the write path,
so N processes can share one directory without write-write races.  A
restarting historical re-runs the normal storage recovery (snapshot
mmap + WAL replay past the watermark) and is 503-busy until replay
finishes; its replicas carry the traffic meanwhile.

In-process use (tests; kill = `shutdown()`, restart = a fresh node on
the same directory):

    node = HistoricalNode("h0", storage_dir).start()
    ... node.url ...
    node.shutdown()

Subprocess use (bench; real SIGKILL):

    python -m spark_druid_olap_tpu.cluster.historical \
        --storage-dir DIR --node-id h0 --port 0 --announce FILE

writes {"node_id", "port", "url", "pid"} to FILE once serving.
"""

from __future__ import annotations

import dataclasses
from typing import Optional

from ..utils.log import get_logger

log = get_logger("cluster.historical")


class HistoricalNode:
    """One in-process historical: context + HTTP server over a shared
    snapshot store."""

    def __init__(
        self,
        node_id: str,
        storage_dir: str,
        host: str = "127.0.0.1",
        port: int = 0,
        config=None,
    ):
        self.node_id = node_id
        self.storage_dir = storage_dir
        self.host = host
        self._want_port = port
        self.ctx = None
        self.server = None
        self._config = config

    def start(self) -> "HistoricalNode":
        from ..api import TPUOlapContext
        from ..config import SessionConfig
        from ..server import OlapServer

        cfg = self._config or SessionConfig.load_calibrated()
        # read-only consumer of the shared store: no fsync (this node
        # never journals), no background flush sweep, no compaction —
        # the broker owns the write path
        cfg = dataclasses.replace(
            cfg,
            storage_dir=self.storage_dir,
            storage_fsync=False,
            snapshot_flush_s=0.0,
            compaction_interval_s=0.0,
        )
        self.ctx = TPUOlapContext(cfg)
        # the id the scatter surface stamps on every partial response
        self.ctx.cluster_node_id = self.node_id
        self.server = OlapServer(
            self.ctx, host=self.host, port=self._want_port
        )
        self.server.start()
        log.info(
            "historical %s serving %s on %s", self.node_id,
            self.storage_dir, self.url,
        )
        return self

    @property
    def port(self) -> int:
        return self.server.port if self.server else 0

    @property
    def url(self) -> str:
        return f"http://{self.host}:{self.port}"

    def shutdown(self) -> None:
        if self.server is not None:
            self.server.shutdown()
            self.server = None


def main(argv: Optional[list] = None) -> int:
    import argparse
    import json
    import os
    import signal
    import threading

    ap = argparse.ArgumentParser(
        prog="spark_druid_olap_tpu.cluster.historical",
        description="serve one historical replica over a shared "
        "snapshot store",
    )
    ap.add_argument("--storage-dir", required=True)
    ap.add_argument("--node-id", required=True)
    ap.add_argument("--host", default="127.0.0.1")
    ap.add_argument("--port", type=int, default=0)
    ap.add_argument(
        "--announce",
        help="write {node_id, port, url, pid} JSON here once serving "
        "(how the bench driver finds ephemeral ports)",
    )
    args = ap.parse_args(argv)
    from ..utils import compile_cache

    compile_cache.enable()
    node = HistoricalNode(
        args.node_id, args.storage_dir, host=args.host, port=args.port
    ).start()
    if args.announce:
        from ..catalog.persist import atomic_write_json

        atomic_write_json(
            args.announce,
            {
                "node_id": node.node_id,
                "port": node.port,
                "url": node.url,
                "pid": os.getpid(),
            },
        )
    stop = threading.Event()
    signal.signal(signal.SIGTERM, lambda *_: stop.set())
    signal.signal(signal.SIGINT, lambda *_: stop.set())
    stop.wait()
    node.shutdown()
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
