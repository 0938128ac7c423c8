"""Kernels of the serving path compiled, at the benchmark's shapes, by the
TPU's own compiler for a chip that is described and not attached.

Interpret mode cannot show what XLA does AROUND a Mosaic call: the kernel
wants its operands in the order written, and XLA keeps an array in the
layout that pads least, so a kernel handed the wrong view compiles, runs
right, and copies the whole operand in and out on every call.  Nothing
runs here and no number of the device comes out of it.

The topology is described inside a fixture, never at import: only the
worker that is given this file loads the TPU's library.
"""

import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from ml_trainer_tpu.ops.kernels.slot_cache_write import (
    _position_on_lanes,
    slot_cache_write,
)


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.mark.parametrize("shape,layout", [
    ((32, 20, 1024, 64), "{2,3,1,0"),    # gpt2-large.batch-decode: 20 x 64
    ((32, 8, 1024, 128), "{3,2,1,0"),    # heads of 128: the order as written
], ids=["position_on_lanes", "position_on_sublanes"])
def test_slot_cache_write_leaves_the_donated_cache_in_place(
        one_chip, shape, layout):
    """The write compiled with the cache donated: XLA's layout of the cache
    is the one ``_position_on_lanes`` foresees, the Mosaic call is there,
    and no copy, no scatter and no temporary of the cache's size is."""
    b, h, L, d = shape

    def spec(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    compiled = jax.jit(
        lambda kc, vc, kn, vn, pos: slot_cache_write(
            kc, vc, kn, vn, pos, implementation="pallas"),
        donate_argnums=(0, 1),
    ).lower(spec(shape), spec(shape), spec((b, h, 1, d)), spec((b, h, 1, d)),
            spec((b,), jnp.int32)).compile()
    text = compiled.as_text()
    entry = re.search(r"entry_computation_layout=\{\((.*?)\)->", text).group(1)
    assert entry.startswith(f"bf16[{b},{h},{L},{d}]{layout}"), entry[:80]
    assert _position_on_lanes(L, d) == (layout == "{2,3,1,0")
    assert text.count('custom_call_target="tpu_custom_call"') == 1
    assert "slot_cache_write" in text
    cache = rf"bf16\[{b},{h},(?:{L},{d}|{d},{L})\]"
    assert not re.findall(rf"= {cache}\S* (?:copy|scatter)\(", text)
    assert " while(" not in text
    cache_bytes = 2 * b * h * L * d
    assert compiled.memory_analysis().temp_size_in_bytes < cache_bytes // 64
