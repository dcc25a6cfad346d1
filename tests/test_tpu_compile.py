"""Compile the served path's Pallas kernels for a described TPU v5e.

Interpret mode (every other test here) cannot see what the chip's compiler
refuses: tile-misaligned slices and DMAs, unsupported in-kernel reshapes,
VMEM overruns.  These tests lower and compile the kernels at the real
widths for a ``v5e:2x2`` topology that is described, not attached — no
chip is needed, and nothing runs.

The topology is described inside a module-scoped fixture, never at import
time: only one process may load the TPU library, and the test runner's
workers all import this file.
"""

import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P, SingleDeviceSharding

from repro.core import distributed
from repro.kernels import filter_kernel, ops

LANES = (4, 8, 16)
# the largest buckets the engines produce: a serving group of 8 requests at
# 1000 distinct keys each pads to 8192 query columns; 64k candidate rows is
# eight 8192-row bucket steps
ROWS, QUERIES = 1 << 16, 1 << 13
# the table-count extremes: one 128-table tile at the largest row block, and
# the scatter-tile cap at the smallest
TABLES = (128, filter_kernel.FUSED_MAX_TABLES)


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


@pytest.mark.parametrize("lanes", LANES)
@pytest.mark.parametrize("n_tables", TABLES)
@pytest.mark.parametrize("with_elig", (True, False))
@pytest.mark.parametrize("mode", ("sum", "any"))
def test_filter_table_counts_compiles(one_chip, lanes, n_tables, with_elig, mode):
    block_n = filter_kernel.fused_block_n(n_tables)
    # mode='any' takes the whole query range in one block (the distributed
    # filter pads queries to 128-multiples); 'sum' tiles it
    q = 256 if mode == "any" else QUERIES
    block_q = q if mode == "any" else filter_kernel.DEFAULT_BLOCK_Q
    elig = (
        (_spec((ROWS,), jnp.int32, one_chip), _spec((q,), jnp.int32, one_chip))
        if with_elig
        else None
    )
    compiled = filter_kernel.filter_table_counts.lower(
        _spec((lanes, ROWS), jnp.uint32, one_chip),
        _spec((lanes, q), jnp.uint32, one_chip),
        elig,
        _spec((ROWS,), jnp.int32, one_chip),
        n_tables=n_tables, n_queries=q - 3, block_n=block_n,
        block_q=block_q, mode=mode,
    ).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize(
    "lanes,store_lanes", [(4, 4), (8, 8), (16, 16), (4, 16)]
)
@pytest.mark.parametrize("n_tables", TABLES)
def test_gather_filter_table_counts_compiles(one_chip, lanes, store_lanes, n_tables):
    # a 1M-row store: the packed layout must cost its logical bytes in HBM
    n_store = 1 << 20
    n_lines = n_store * store_lanes // filter_kernel.STORE_LINE
    compiled = filter_kernel.gather_filter_table_counts.lower(
        _spec((ROWS,), jnp.int32, one_chip),
        _spec((n_lines, filter_kernel.STORE_LINE), jnp.uint32, one_chip),
        _spec((lanes, QUERIES), jnp.uint32, one_chip),
        (_spec((ROWS,), jnp.int32, one_chip), _spec((QUERIES,), jnp.int32, one_chip)),
        _spec((ROWS,), jnp.int32, one_chip),
        store_lanes=store_lanes, n_tables=n_tables, n_queries=QUERIES - 5,
        block_n=filter_kernel.fused_block_n(n_tables),
        block_q=filter_kernel.DEFAULT_BLOCK_Q,
    ).compile()
    assert "tpu_custom_call" in compiled.as_text()
    logical = {
        "store": n_store * store_lanes * 4,
        "elig": (ROWS + QUERIES) * 4,
        "rows+seg": 2 * ROWS * 4,
        "queries": lanes * QUERIES * 4,
    }
    # no operand is padded: arguments cost what they hold (small operands
    # may round up to a whole tile)
    got = compiled.memory_analysis().argument_size_in_bytes
    assert sum(logical.values()) <= got <= sum(logical.values()) + (64 << 10)


@pytest.mark.parametrize("lanes", LANES)
@pytest.mark.parametrize("n_tables", TABLES)
def test_routed_mesh_body_compiles(topo, monkeypatch, lanes, n_tables):
    # the routed body asks the default backend whether to interpret; steer
    # it to the chip path so the kernel is compiled, not interpreted
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    mesh = Mesh(np.asarray(topo.devices), ("shard",))
    rows_sh = NamedSharding(mesh, P(("shard",)))
    pad_store, pad_items, q = 1 << 18, 1 << 14, 1000
    qb = ops._pow2_bucket(q, ops._FALLBACK_MIN_Q)
    fn = distributed._routed_local_counts_fn(
        mesh, ("shard",), pad_items, qb, q, n_tables, "fused"
    )
    n = len(topo.devices)
    compiled = fn.lower(
        _spec((n * pad_store, lanes), jnp.uint32, rows_sh),
        _spec((n * pad_items,), jnp.int32, rows_sh),
        _spec((n * pad_items,), jnp.int32, rows_sh),
        _spec((n * pad_items,), jnp.int32, rows_sh),
        _spec((qb,), jnp.int32, NamedSharding(mesh, P())),
        _spec((qb, lanes), jnp.uint32, NamedSharding(mesh, P())),
    ).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text and "all-reduce" in text
