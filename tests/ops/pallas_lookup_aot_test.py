"""The windowed lookup compiles for a v5e at LOKI's widths.

Interpret mode cannot show what Mosaic refuses (a misaligned slice, too
much VMEM). libtpu is installed here, so the kernel is compiled for a
chip that is described and not attached: lowering, not results (those
are ``scripts/tpu_kernel_check.py --lookup``'s, on the chip). The
topology is described inside a fixture of this one file: only the xdist
worker that is given the file loads the TPU's library.
"""

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from esslivedata_tpu.ops import pallas_lookup


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies

    patch = pytest.MonkeyPatch()
    patch.setenv("TPU_LOG_DIR", "disabled")
    patch.setenv("TPU_ACCELERATOR_TYPE", "v5litepod-4")
    patch.setenv("TPU_WORKER_HOSTNAMES", "localhost")
    patch.setenv("TPU_SKIP_MDS_QUERY", "1")
    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as error:  # no libtpu, or its lock is held
        pytest.skip(f"no v5e:2x2 topology can be described here: {error}")
    finally:
        patch.undo()
    return SingleDeviceSharding(topo.devices[0])


@pytest.mark.parametrize("n_pix", [802_816, 172_032])
def test_kernel_and_items_compile_at_lokis_widths(one_chip, n_pix):
    n = 1 << 22
    packed = jax.ShapeDtypeStruct((208, n_pix), jnp.bfloat16, sharding=one_chip)
    keys = jax.ShapeDtypeStruct((n,), jnp.int32, sharding=one_chip)
    compiled = (
        jax.jit(lambda t, k: pallas_lookup._lookup_sorted(t, k, 8, False))
        .lower(packed, keys)
        .compile()
    )
    assert "tpu_custom_call" in compiled.as_text()
    stats = compiled.memory_analysis()
    # the table is read in place: no second copy, no relayout of it
    assert stats.temp_size_in_bytes < packed.size * 2 // 8
