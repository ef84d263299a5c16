"""The main-path Pallas kernels compile for a TPU v5e at their real widths.

Each test lowers one kernel with ``interpret=False`` against a v5e chip
that is described, not attached (``jax.experimental.topologies``), so
Mosaic refusals — an unaligned block, a primitive with no TPU lowering,
more scoped VMEM than a kernel may hold — fail here on a CPU host instead
of on the chip. Nothing runs: a compile says nothing about results or
times.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU compiler library, and every test worker
imports this file.
"""
import os

import jax
import jax.numpy as jnp
import pytest

from repro.estimator.model import EstimatorConfig
from repro.estimator.ssm import SSMConfig
from repro.sim.engine import est_chunk_rows

E = EstimatorConfig()  # the paper's estimator widths
ROWS = est_chunk_rows(E)  # rows per estimator forward at that width
PERIODS = 8  # report periods in one featurize slab
N_CELLS = 4
SLOTS = 1024


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler library, or it is held
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without one: keep the cache out of it
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield jax.sharding.SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)


def _featurize():
    from repro.kernels.featurize.kernel import featurize
    return (lambda k, c, s: featurize(k, c, s, E.window, interpret=False),
            [((ROWS, PERIODS + E.window - 1, E.n_kpms), jnp.float32),
             ((E.n_kpms,), jnp.float32), ((E.n_kpms,), jnp.float32)])


def _lstm_scan_q():
    from repro.kernels.lstm.kernel import lstm_scan_q
    g = 4 * E.lstm_hidden
    return (lambda x, a, sa, b, sb, bias: lstm_scan_q(
        x, a, sa, b, sb, bias, interpret=False),
        [((ROWS, E.window, E.n_kpms), jnp.float32),
         ((g, E.n_kpms), jnp.int8), ((g, 1), jnp.float32),
         ((g, E.lstm_hidden), jnp.int8), ((g, 1), jnp.float32),
         ((g,), jnp.float32)])


def _qmm():
    from repro.kernels.qmm.kernel import qmm
    return (lambda x, sx, w, sw: qmm(x, sx, w, sw, interpret=False),
            [((ROWS, E.cnn_flat), jnp.int8), ((ROWS, 1), jnp.float32),
             ((E.hidden, E.cnn_flat), jnp.int8),
             ((E.hidden, 1), jnp.float32)])


def _qmm_one_output():
    from repro.kernels.qmm.kernel import qmm
    return (lambda x, sx, w, sw: qmm(x, sx, w, sw, interpret=False),
            [((ROWS, E.hidden), jnp.int8), ((ROWS, 1), jnp.float32),
             ((1, E.hidden), jnp.int8), ((1, 1), jnp.float32)])


def _quantize():
    from repro.kernels.quant.kernel import quantize
    # the int8 CNN fc weight, quantized as (hidden, 78,624) rows
    return (lambda x: quantize(x, interpret=False),
            [((E.hidden, E.cnn_flat), jnp.float32)])


def _segsum():
    from repro.kernels.segsum.kernel import segment_reduce_batched
    return (lambda v, g: segment_reduce_batched(v, g, N_CELLS,
                                                interpret=False),
            [((1, SLOTS), jnp.float32), ((1, SLOTS), jnp.int32)])


def _ssd():
    from repro.kernels.ssd.kernel import ssd
    c = SSMConfig()
    b, s = 256, 2 * c.chunk
    return (lambda x, dt, a, bm, cm: ssd(x, dt, a, bm, cm, chunk=c.chunk,
                                         interpret=False),
            [((b, s, c.n_heads, c.head_dim), jnp.float32),
             ((b, s, c.n_heads), jnp.float32), ((c.n_heads,), jnp.float32),
             ((b, s, c.n_groups, c.state_dim), jnp.float32),
             ((b, s, c.n_groups, c.state_dim), jnp.float32)])


KERNELS = {"featurize": _featurize, "lstm_scan_q": _lstm_scan_q,
           "qmm": _qmm, "qmm_one_output": _qmm_one_output,
           "quantize": _quantize, "segsum": _segsum, "ssd": _ssd}


@pytest.mark.parametrize("name", sorted(KERNELS))
def test_kernel_compiles_for_v5e(one_chip, name):
    fn, specs = KERNELS[name]()
    args = [jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
            for shape, dtype in specs]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
