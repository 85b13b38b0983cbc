"""The solver's Pallas kernels compile for a TPU v5e at real widths.

Interpret mode (tests/test_kernels.py) runs the kernel bodies but never
meets the TPU compiler's tiling and VMEM rules; these tests compile for a
described (not attached) v5e chip.  Shapes are the ``kernels/ops.py``
wrappers' padded ones: 2^20 ELL rows with k = 8 (road) and k = 32
(26-connected grid), 2^21 COO edges, and 256 dense blocks of 128 and 512.
The last test compiles the scanned IRLS program ``chip_smoke.py`` runs.

The topology is described inside a module-scoped fixture, never at import:
only one process may load the TPU compiler's library at a time.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

ROWS = 2 ** 20
EDGES = 2 ** 21
BLOCKS = 256


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def _spec(sharding, shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compiled_text(fn, *args) -> str:
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text      # the kernel is in the program
    return text


@pytest.mark.parametrize("k", [8, 32])
def test_ell_spmv_compiles_for_v5e(one_chip, k):
    from repro.kernels.ell_spmv import ell_spmv_pallas
    s = lambda *shape: _spec(one_chip, shape)
    _compiled_text(ell_spmv_pallas, s(ROWS, k), s(ROWS, k), s(ROWS), s(ROWS))


@pytest.mark.parametrize("k", [8, 32])
def test_fused_ell_sweep_compiles_for_v5e(one_chip, k):
    from repro.kernels.edge_reweight import fused_ell_sweep_pallas
    s = lambda *shape: _spec(one_chip, shape)
    _compiled_text(fused_ell_sweep_pallas, s(ROWS, k), s(ROWS, k), s(ROWS),
                   s(ROWS), s(ROWS), s())


def test_edge_reweight_compiles_for_v5e(one_chip):
    from repro.kernels.edge_reweight import edge_reweight_pallas
    s = lambda *shape: _spec(one_chip, shape)
    _compiled_text(edge_reweight_pallas, s(EDGES), s(EDGES), s())


@pytest.mark.parametrize("bs", [128, 512])
def test_block_diag_matvec_compiles_for_v5e(one_chip, bs):
    from repro.kernels.block_diag_matmul import block_diag_matvec_pallas
    s = lambda *shape: _spec(one_chip, shape)
    _compiled_text(block_diag_matvec_pallas, s(BLOCKS, bs, bs), s(BLOCKS, bs))


def test_smoke_scanned_program_compiles_for_v5e(one_chip, monkeypatch):
    """chip_smoke.py's scanned program (ELL layout, Pallas kernels, fused
    sweep) on a small road instance.  The process's backend is the CPU, so
    the kernel wrappers would pick interpret mode: steer them to the TPU
    path here."""
    import chip_smoke
    from repro.core import MinCutSession
    from repro.kernels import ops
    from repro.launch.solve import build_instance

    monkeypatch.setattr(ops, "_interpret", lambda: False)
    prob = chip_smoke.build_problem(build_instance("road", 40, 0))
    cfg = chip_smoke.scanned_config(prob.n_blocks)
    run, args = MinCutSession(prob, cfg, backend="scanned").scanned_program()
    abstract = [_spec(one_chip, a.shape, a.dtype) for a in args]
    text = run.lower(*abstract).compile().as_text()
    assert "tpu_custom_call" in text


def test_host_step_compiles_for_v5e(one_chip):
    """The host IRLS step the benchmark cells run (COO layout, dense block
    Jacobi): its block factorization sits in a conditional branch, gated on
    the warm start's residual, and the TPU compiler keeps it there."""
    from repro.core import IRLSConfig, MinCutSession
    from repro.core.irls import _Stepper
    from repro.launch.solve import build_instance

    cfg = IRLSConfig(n_blocks=8)
    sess = MinCutSession(build_instance("road", 40, 0), cfg, profile=False)
    block_plan, ell_plan = sess._plans_for(cfg)
    st = _Stepper(sess.problem.device_graph(jnp.float32), cfg, block_plan,
                  ell_plan)
    g = st.g
    s = lambda a: _spec(one_chip, jnp.shape(a), jnp.result_type(a))
    text = st._jit_step.lower(s(g.c_s), s(1e-6), s(1e-3), s(g.c), s(g.c_s),
                              s(g.c_t), None, first=False).compile().as_text()
    assert " conditional(" in text
    assert 'custom_call_target="Cholesky"' in text
