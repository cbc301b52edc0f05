"""Test harness: force an 8-device CPU mesh so multi-chip sharding paths run
without TPU hardware (SURVEY.md §4: the fake multi-node backend the reference
never had — its tests demanded a live Druid cluster; ours demand nothing)."""

import os

flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()

# tests run on the CPU, whatever the machine holds
os.environ.setdefault("JAX_PLATFORMS", "cpu")

import jax  # noqa: E402,F401

import numpy as np
import pytest

from spark_druid_olap_tpu.catalog.segment import build_datasource
from spark_druid_olap_tpu.utils import datagen


@pytest.fixture(scope="session")
def lineitem_ds():
    cols = datagen.gen_lineitem(scale=0.005, seed=42)  # ~30k rows
    return build_datasource(
        "tpch",
        cols,
        dimension_cols=datagen.LINEITEM_DIMS,
        metric_cols=datagen.LINEITEM_METRICS,
        time_col="l_shipdate",
        rows_per_segment=8192,  # several segments to exercise merge
    )


@pytest.fixture(scope="session")
def lineitem_cols():
    return datagen.gen_lineitem(scale=0.005, seed=42)


@pytest.fixture(scope="session")
def ssb_ds():
    cols = datagen.gen_ssb_lineorder_flat(scale=0.005, seed=7)
    return build_datasource(
        "ssb",
        cols,
        dimension_cols=datagen.SSB_DIMS,
        metric_cols=datagen.SSB_METRICS,
        time_col="lo_orderdate",
        rows_per_segment=16384,
    )


@pytest.fixture(scope="session")
def ssb_cols():
    return datagen.gen_ssb_lineorder_flat(scale=0.005, seed=7)
