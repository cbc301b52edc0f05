"""Package logging (SURVEY.md §5 metrics/logging row).

Reference parity: the reference threads Spark's log4j `Logging` trait through
planner and client code — plan decisions at debug, query dispatch at info.
Here the standard `logging` module plays that role under the
`spark_druid_olap_tpu` namespace; nothing configures the root logger (library
etiquette), so output appears only when the application enables it:

    import logging
    logging.getLogger("spark_druid_olap_tpu").setLevel(logging.INFO)
    logging.basicConfig()

Conventions: plan/rewrite decisions -> DEBUG; per-query completion with the
QueryMetrics one-liner -> INFO; retries/fallbacks (sparse
overflow, transient re-dispatch) -> WARNING.
"""

from __future__ import annotations

import logging


def get_logger(name: str) -> logging.Logger:
    """Child logger under the package namespace: get_logger("exec.engine")
    -> "spark_druid_olap_tpu.exec.engine"."""
    return logging.getLogger(f"spark_druid_olap_tpu.{name}")
