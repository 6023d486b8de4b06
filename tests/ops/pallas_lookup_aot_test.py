"""The Q step's kernels compile for a v5e at the cells' widths: the
windowed lookup at LOKI's (one byte plane) and at DREAM's powder widths
(two), the factorised one-hot bincount on the MXU (``bincount_mxu``)
alone at DREAM's powder and at BIFROST's widths, the step's other side
(XLA's gather and scatter) at DREAM's powder widths, and the whole step
in each of the three ways of counting bins: powder's (two planes in
front of 34 000 bins) and both of BIFROST's merged stream (a tiny
two-plane table in front of 4 800 and of 10 000 bins).

Interpret mode cannot show what Mosaic refuses (a misaligned slice, too
much VMEM). libtpu is installed here, so the kernel is compiled for a
chip that is described and not attached: lowering, not results (those
are ``scripts/tpu_kernel_check.py --lookup``'s and ``--bincount``'s, on
the chip). The
topology is described inside a fixture of this one file: only the xdist
worker that is given the file loads the TPU's library.
"""

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from esslivedata_tpu.ops import pallas_hist, pallas_lookup


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
    packed = jax.ShapeDtypeStruct((1, 208, n_pix), jnp.bfloat16, sharding=one_chip)
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


@pytest.mark.parametrize("n_pix", [491_520, 30_720])
def test_two_plane_kernel_and_items_compile_at_dreams_powder_widths(one_chip, n_pix):
    """An int32 table over 34 000 bins as two byte planes, 500 TOA bins
    padded to 512 (a 28-bit key for the mantle), a 4 Mi batch."""
    n = 1 << 22
    table = jax.ShapeDtypeStruct((n_pix, 500), jnp.int32)
    packed = jax.eval_shape(
        lambda t: pallas_lookup.pack_table(t, planes=2), table
    )
    assert packed.shape == (2, 512, n_pix) and packed.dtype == jnp.bfloat16
    assert pallas_lookup.lookup_kind(n, packed.shape) == "windowed"
    packed = jax.ShapeDtypeStruct(packed.shape, packed.dtype, sharding=one_chip)
    keys = jax.ShapeDtypeStruct((n,), jnp.int32, sharding=one_chip)
    compiled = (
        jax.jit(lambda t, k: pallas_lookup._lookup_sorted(t, k, 9, False))
        .lower(packed, keys)
        .compile()
    )
    assert "tpu_custom_call" in compiled.as_text()
    stats = compiled.memory_analysis()
    # both planes are read in place: no second copy, no relayout
    assert stats.temp_size_in_bytes < packed.size * 2 // 8


@pytest.mark.parametrize(
    "shape", [(2, 512, 491_520), (1, 208, 802_816)], ids=["dream", "loki"]
)
def test_a_batch_under_the_crossover_gathers_from_the_planes_in_place(
    one_chip, shape
):
    """The fallback inside ``lookup``: one element gather a plane. A
    slice over the planes had XLA copy the whole table into another
    layout first (1 GB and 3.2 ms a step for the mantle; my chip run,
    PR 32)."""
    n = 1 << 15
    assert pallas_lookup.lookup_kind(n, shape) == "gather"
    packed = jax.ShapeDtypeStruct(shape, jnp.bfloat16, sharding=one_chip)
    index = jax.ShapeDtypeStruct((n,), jnp.int32, sharding=one_chip)
    ok = jax.ShapeDtypeStruct((n,), jnp.bool_, sharding=one_chip)
    compiled = jax.jit(pallas_lookup.lookup).lower(packed, index, index, ok).compile()
    assert "tpu_custom_call" not in compiled.as_text()
    assert compiled.memory_analysis().temp_size_in_bytes < packed.size * 2 // 8


@pytest.mark.parametrize("n_pix", [491_520, 157_696])
def test_the_gather_side_compiles_at_dreams_powder_widths(one_chip, n_pix):
    """The step's other side (an int32 table, 2000 x 17 = 34 000 bins:
    XLA's element gather and XLA's scatter-add), at the widths of
    ``dream_powder.paced14``: the table is taken as it is, 500 columns
    unpadded, and read in place."""
    import functools

    from esslivedata_tpu.ops.qhistogram import table_scatter_delta

    n = 1 << 22
    step = functools.partial(
        table_scatter_delta, id_base=1, lo=0.0, hi=1e9 / 14, inv_width=500 * 14 / 1e9,
        n_bins=34_000, dtype=jnp.float32, method="scatter",
    )
    table = jax.ShapeDtypeStruct((n_pix, 500), jnp.int32, sharding=one_chip)
    ids = jax.ShapeDtypeStruct((n,), jnp.int32, sharding=one_chip)
    toa = jax.ShapeDtypeStruct((n,), jnp.float32, sharding=one_chip)
    compiled = jax.jit(step).lower(table, ids, toa).compile()
    text = compiled.as_text()
    assert " gather(" in text and " scatter(" in text and "tpu_custom_call" not in text
    stats = compiled.memory_analysis()
    table_bytes = n_pix * 500 * 4
    assert stats.argument_size_in_bytes < table_bytes + 2 * n * 4 + (16 << 20)  # no padding to 512 columns
    assert stats.temp_size_in_bytes < table_bytes // 8  # no second copy of the table


@pytest.mark.parametrize(
    "n, n_bins",
    [(1 << 22, 34_000), (1 << 24, 4_800), (1 << 24, 10_000), (1 << 22, 65_536)],
    ids=["powder", "qe_map", "elastic_qmap", "one_tile"],
)
def test_the_mxu_bincount_compiles_alone(one_chip, n, n_bins):
    """The count tile and both one-hots live in VMEM: beside the bins
    the kernel reads and the counts it writes, nothing on the HBM."""
    bins = jax.ShapeDtypeStruct((n,), jnp.int32, sharding=one_chip)
    compiled = (
        jax.jit(lambda b: pallas_hist.bincount_mxu(b, n_bins, interpret=False))
        .lower(bins)
        .compile()
    )
    text = compiled.as_text()
    assert text.count("tpu_custom_call") == 1 and " scatter(" not in text
    assert compiled.memory_analysis().temp_size_in_bytes < 4 << 20


def _whole_step(one_chip, *, n, table, packed_shape, n_bins, method):
    """``table_scatter_delta`` behind a two-plane packed ``table``,
    compiled for the described chip: its HLO and its memory."""
    import functools

    from esslivedata_tpu.ops.qhistogram import table_scatter_delta

    n_pix, n_toa = table.shape
    packed = jax.eval_shape(lambda t: pallas_lookup.pack_table(t, planes=2), table)
    assert packed.shape == packed_shape and pallas_lookup.lookup_kind(n, packed.shape) == "windowed"
    step = functools.partial(
        table_scatter_delta, id_base=1, lo=0.0, hi=1e9 / 14, inv_width=n_toa * 14 / 1e9,
        n_bins=n_bins, dtype=jnp.float32, method=method, packed_shape=(n_pix, n_toa),
    )
    packed = jax.ShapeDtypeStruct(packed.shape, packed.dtype, sharding=one_chip)
    ids = jax.ShapeDtypeStruct((n,), jnp.int32, sharding=one_chip)
    toa = jax.ShapeDtypeStruct((n,), jnp.float32, sharding=one_chip)
    compiled = jax.jit(step).lower(packed, ids, toa).compile()
    return compiled.as_text(), compiled.memory_analysis()


@pytest.mark.parametrize("n_pix", [491_520, 30_720])
def test_powders_whole_step_compiles_with_the_mxu_bincount(one_chip, monkeypatch, n_pix):
    """``dream_powder.paced14`` on a TPU: two byte planes in front of
    34 000 bins, a 4 Mi batch: the lookup's kernel, then the
    bincount's, and no scatter."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    text, stats = _whole_step(
        one_chip, n=1 << 22, table=jax.ShapeDtypeStruct((n_pix, 500), jnp.int32),
        packed_shape=(2, 512, n_pix), n_bins=34_000, method="mxu",
    )
    assert text.count("tpu_custom_call") == 2 and " scatter(" not in text
    assert stats.temp_size_in_bytes < 1 << 30  # a few 4 Mi temporaries, no copy of the table


@pytest.mark.parametrize(
    "n_bins, method, kernels",
    [
        (80 * 60, "pallas", 2),
        (100 * 100, "scatter", 1),
        (80 * 60, "mxu", 2),
        (100 * 100, "mxu", 2),
    ],
    ids=["qe_map", "elastic_qmap", "qe_map_mxu", "elastic_qmap_mxu"],
)
def test_both_whole_steps_compile_at_bifrosts_widths(one_chip, monkeypatch, n_bins, method, kernels):
    """``bifrost_qe.paced14``: 13 500 pixels x 320 TOA bins in two byte
    planes (106 table windows against 16 384 event blocks), the cell's
    16 Mi merged batch. Each bin space in each way it can be counted:
    S(Q, E)'s 4 800 bins by the flat one-hot (38 lane groups where
    LOKI's 100 bins are one) and by the factorised one on the MXU, which
    ``method="auto"`` takes there on a TPU; the elastic map's 10 000
    (past ``MAX_PALLAS_BINS``) by XLA's scatter and on the MXU, one
    more kernel than the lookup's and no scatter. The step asks
    ``jax.default_backend()`` whether to interpret its kernels; here it
    is told the backend it is being compiled for."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    text, stats = _whole_step(
        one_chip, n=1 << 24, table=jax.ShapeDtypeStruct((13_500, 320), jnp.int16),
        packed_shape=(2, 320, 13_568), n_bins=n_bins, method=method,
    )
    assert text.count("tpu_custom_call") == kernels and (" scatter(" in text) == (method == "scatter")
    # the wire, the table and a few 16 Mi temporaries: nothing near the chip's 16 GB
    assert stats.temp_size_in_bytes < 1 << 30


@pytest.mark.parametrize(
    "n_screen, n_pix, replicas, n, sorts",
    [
        (256 * 256, 802_816, 4, 1 << 20, 1),
        (1280 * 1280, None, None, 1 << 22, 1),
        (256, None, None, 1 << 22, 0),
    ],
    ids=["loki_bank_view", "nmx_panel", "dream_one_block"],
)
def test_the_detector_views_mxu_count_compiles_at_the_cells_widths(
    one_chip, monkeypatch, n_screen, n_pix, replicas, n, sorts
):
    """``EventHistogrammer(method="mxu")`` (ADR 0131), its whole fused
    step at the widths of the three detector cells, 100 TOA bins: LOKI's
    bank view (a 4-replica LUT gathered on the chip, 4 Mi slots into
    6.55 M bins), NMX's panel (the host's flat wire into 164 M bins) and
    DREAM's smallest view (one block: no sort). One Mosaic kernel, no
    XLA scatter, the key sort where there is more than one block."""
    import numpy as np

    from esslivedata_tpu.ops.histogram import EventHistogrammer, HistogramState

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    lut = None if replicas is None else np.zeros((replicas, n_pix), np.int32)
    hist = EventHistogrammer(
        toa_edges=np.linspace(0.0, 1e9 / 14, 101), n_screen=n_screen,
        pixel_lut=lut, method="auto",
    )
    assert hist.fuse_key[1] == "mxu"
    window = jax.ShapeDtypeStruct((hist._n_state,), jnp.float32, sharding=one_chip)
    states = (HistogramState(window, window, None),)
    if lut is None:
        step = hist._step_flat_fused_impl
        wire = (jax.ShapeDtypeStruct((n,), jnp.int32, sharding=one_chip),)
    else:
        step = hist._step_fused_impl
        wire = (
            jax.ShapeDtypeStruct(lut.shape, jnp.int32, sharding=one_chip),
            jax.ShapeDtypeStruct((n,), jnp.int32, sharding=one_chip),
            jax.ShapeDtypeStruct((n,), jnp.float32, sharding=one_chip),
        )
    text = jax.jit(step, donate_argnums=(0,)).lower(states, *wire).compile().as_text()
    assert text.count("tpu_custom_call") == 1 and " scatter(" not in text
    assert text.count(" sort(") == sorts
