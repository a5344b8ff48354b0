"""Real-size compiles of the main path's device programs for a v5e that
is described, not attached: what the chip's compiler refuses (scoped
VMEM, tiling, HBM fit) fails here at no chip time. Nothing runs, so
these say nothing about results or speed.

The topology is described inside a fixture, never at import: only one
process may load the TPU library, and every xdist worker imports this
file."""

import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

TABLE_ROWS = 204_795     # the seeded trivy-db-shaped table (db/synth.py)
TOKENS = 40
MID_T_PAD = 1 << 18      # detectd warmup's top rung
Q_PAD = 4096
U_PAD = 1024


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def test_shiftor_kernel_compiles_at_engine_shape(one_chip):
    from trivy_tpu.ops import shiftor_pallas as sp
    from trivy_tpu.secret.engine import CHUNK_LEN, SecretScanner
    bank = SecretScanner(use_device=False)._bank
    kww, kwm, bit = sp.pack_bank(bank)
    compiled = sp.shiftor.lower(
        _sds(kww.shape, jnp.int32, one_chip),
        _sds(kwm.shape, jnp.int32, one_chip),
        _sds(bit.shape, jnp.int32, one_chip),
        _sds((64, CHUNK_LEN), jnp.uint8, one_chip),
        n_words=bank.words).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_csr_pair_join_compact_compiles_mid_ladder(one_chip):
    from trivy_tpu.db.table import RawAdvisory, build_table
    from trivy_tpu.detect.engine import BatchDetector
    from trivy_tpu.ops import join as J
    tiny = build_table([RawAdvisory(
        source="alpine 3.19", ecosystem="alpine", pkg_name="p",
        vuln_id="CVE-1", fixed_version="1.0-r0")])
    assert tiny.lo_tok.shape[1] == TOKENS
    det = BatchDetector(tiny)
    try:
        h_cap = det._hit_capacity(MID_T_PAD, budget=1 / 16)
    finally:
        det.close()
    assert 0 < h_cap < MID_T_PAD
    compiled = J.csr_pair_join_compact.lower(
        _sds((TABLE_ROWS, TOKENS), tiny.lo_tok.dtype, one_chip),
        _sds((TABLE_ROWS, TOKENS), tiny.hi_tok.dtype, one_chip),
        _sds((TABLE_ROWS,), tiny.flags.dtype, one_chip),
        _sds((U_PAD, TOKENS), jnp.int32, one_chip),
        _sds((Q_PAD,), jnp.int32, one_chip),
        _sds((Q_PAD,), jnp.int32, one_chip),
        _sds((Q_PAD,), jnp.int32, one_chip),
        np.int32(0), MID_T_PAD, h_cap).compile()
    mem = compiled.memory_analysis()
    # the temporaries must leave the chip's 16 GB to the table and
    # the rest of the ladder
    assert mem.temp_size_in_bytes < 4 << 30


def test_sharded_csr_join_compiles_on_dp2_db2(topo):
    from trivy_tpu.parallel.mesh import _sharded_csr_join, mesh_from_devices
    mesh = mesh_from_devices(topo.devices, db_shards=2)
    assert dict(mesh.shape) == {"dp": 2, "db": 2}
    on_db = NamedSharding(mesh, P("db"))
    cells = NamedSharding(mesh, P("dp", "db"))
    rep = NamedSharding(mesh, P())
    a_pad = -(-TABLE_ROWS // 2)
    q_loc = Q_PAD // 4
    compiled = _sharded_csr_join.lower(
        mesh,
        _sds((2, a_pad, TOKENS), jnp.int32, on_db),
        _sds((2, a_pad, TOKENS), jnp.int32, on_db),
        _sds((2, a_pad), jnp.int32, on_db),
        _sds((U_PAD, TOKENS), jnp.int32, rep),
        _sds((2, 2, q_loc), jnp.int32, cells),
        _sds((2, 2, q_loc), jnp.int32, cells),
        _sds((2, 2, q_loc), jnp.int32, cells),
        _sds((2, 2), jnp.int32, cells),
        MID_T_PAD // 4).compile()
    # the table stays where it was placed: no collective moves it
    text = compiled.as_text()
    assert "all-gather" not in text and "all-to-all" not in text
