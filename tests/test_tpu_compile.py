"""Compiles for a DESCRIBED v5e (no chip is attached or needed): what the TPU
compiler makes of the hot path at the benchmark's real shapes. A compile is
not a run: these pin program structure (copies, temporaries), never a time.

The apply's choice of a working size (`ops/sparse.py` "WHAT THE APPLY WORKS
OVER") must not cost a table: PR 29's chip probe measured a plain `lax.switch`
over the four rungs at +9.8 ms a step, a whole-table copy in every branch but
the first and the last, which `_over_unique_prefix`'s `settle` barrier cures.
"""

import os
import re

import pytest

import jax
import jax.numpy as jnp

import openembedding_tpu as embed
from openembedding_tpu.ops.sparse import (FAST_MEMORY_BYTES, apply_ladder,
                                          sparse_apply_packed_table)

N = 4096 * 26  # the benchmark's positions a step


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    from jax.sharding import SingleDeviceSharding
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — whatever keeps libtpu from loading
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # such a compile cannot be read back from the persistent cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


@pytest.mark.parametrize("rows,dim", [(1 << 25, 10), (1 << 22, 64), (1 << 22, 1)],
                         ids=["dim9_2e25x20", "dim64_2e22x128",
                              "dim64_first_order_2e22x2"])
def test_apply_ladder_costs_no_table_copy_on_the_tpu(one_chip, rows, dim):
    """A 2-step scan of the packed apply at the benchmark's table shapes:
    one conditional, the table updated in place in EVERY branch. The 32 MiB
    first-order table is under `FAST_MEMORY_BYTES`: no conditional, and the
    compiler keeps it in fast memory (`S(1)`) as it did (on the chip that
    scatter read 4.2 ms there and 6.1 ms through a conditional, PR 29)."""
    opt = embed.Adagrad(learning_rate=0.05)
    layout = (("accum", dim),)

    def many(packed, ids, grads):
        def body(p, xs):
            return sparse_apply_packed_table(opt, p, layout, dim, *xs)[0], None
        return jax.lax.scan(body, packed, (ids, grads))[0]

    def arg(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    compiled = jax.jit(many, donate_argnums=(0,)).lower(
        arg((rows, 2 * dim), jnp.float32), arg((2, N), jnp.int32),
        arg((2, N, dim), jnp.float32)).compile()
    text = compiled.as_text()
    table = rf"f32\[{rows},{2 * dim}\]"
    scatters = re.findall(rf"= {table}(\S*) fusion\([^\n]*/scatter", text)
    if rows * 2 * dim * 4 < FAST_MEMORY_BYTES:
        assert " conditional(" not in text
        assert len(scatters) == 1 and "S(1)" in scatters[0]
        return
    assert len(apply_ladder(N)) == 4 and text.count(" conditional(") == 1
    assert len(scatters) == 4
    copies = re.findall(rf"= {table}\S* (?:copy|copy-start)\(", text)
    assert not copies, f"{len(copies)} table-sized copies in the program"
    assert compiled.memory_analysis().temp_size_in_bytes < rows * 2 * dim * 4 // 8
