"""Width battery for the collective wrappers added late in r5
(psum_scatter, pscan/exscan) plus edge grids the base file does not
cover: negative/compound ring shifts, dtype sweeps through the
collectives, and prefix sums on multi-element shards.  Reference
analogs: Scan/Exscan/Reduce_scatter in
heat/core/tests/test_communication.py (test_scan, test_exscan,
iscan/iexscan variants — the async forms are XLA scheduling here).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import shard_map as _shard_map
from jax.sharding import PartitionSpec as P

import heat_tpu as ht


@pytest.fixture(scope="module")
def comm():
    return ht.get_comm()


def _smap(comm, body, n_in=1, out=None):
    spec = P(comm.axis_name)
    return jax.jit(
        _shard_map(
            body, mesh=comm.mesh, in_specs=(spec,) * n_in,
            out_specs=out if out is not None else spec,
        )
    )


class TestPrefixSums:
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.int32])
    def test_pscan_scalar_per_rank(self, comm, dtype):
        p = comm.size
        vals = np.arange(1, p + 1)
        x = jnp.asarray(vals, dtype).reshape(p)
        got = _smap(comm, lambda v: comm.pscan(v))(x)
        np.testing.assert_allclose(np.asarray(got), np.cumsum(vals))

    def test_pscan_multielement_shards(self, comm):
        p = comm.size
        x = jnp.arange(3 * p, dtype=jnp.float32)

        def body(v):  # (3,) per shard: elementwise prefix over ranks
            return comm.pscan(v)

        got = np.asarray(_smap(comm, body)(x)).reshape(p, 3)
        want = np.cumsum(np.arange(3 * p, dtype=np.float64).reshape(p, 3), axis=0)
        np.testing.assert_allclose(got, want)

    def test_exscan_zero_at_rank0(self, comm):
        p = comm.size
        vals = np.arange(1, p + 1).astype(np.float32)
        got = np.asarray(_smap(comm, lambda v: comm.exscan(v))(jnp.asarray(vals)))
        want = np.concatenate([[0.0], np.cumsum(vals)[:-1]])
        np.testing.assert_allclose(got, want)

    def test_pscan_matches_offset_computation(self, comm):
        """The canonical use: turning per-rank counts into displacements
        (the reference computes counts_displs this way on the host)."""
        p = comm.size
        counts = np.asarray([(i * 7) % 5 + 1 for i in range(p)])
        got = np.asarray(
            _smap(comm, lambda v: comm.exscan(v))(jnp.asarray(counts, jnp.int32))
        )
        np.testing.assert_array_equal(got, np.concatenate([[0], np.cumsum(counts)[:-1]]))


class TestPrefixSubAxis:
    def test_pscan_on_node_axis(self, comm):
        """An axis_name override addresses the NAMED axis's size, not
        self.size (hierarchical sub-mesh prefix sums)."""
        if comm.size < 4:
            pytest.skip("needs >= 4 devices for a 2-level mesh")
        from heat_tpu.parallel.comm import HierarchicalCommunication

        h = HierarchicalCommunication(grid=(comm.size // 2, 2))
        gx, nx = h.global_axis, h.node_axis
        nodes, per = comm.size // 2, 2
        x = jnp.arange(comm.size, dtype=jnp.float32)

        body = _shard_map(
            lambda v: h.pscan(v, axis_name=nx),
            mesh=h.mesh,
            in_specs=(P((gx, nx)),),
            out_specs=P((gx, nx)),
        )
        got = np.asarray(jax.jit(body)(x)).reshape(nodes, per)
        want = np.cumsum(np.arange(comm.size, dtype=np.float64).reshape(nodes, per), axis=1)
        np.testing.assert_allclose(got, want)


class TestPsumScatter:
    def test_matches_psum_slice(self, comm):
        p = comm.size
        x = jnp.arange(p * p, dtype=jnp.float32)

        def body(v):  # (p,) per shard
            return comm.psum_scatter(v)

        got = np.asarray(_smap(comm, body)(x))
        full = np.asarray(x).reshape(p, p).sum(0)
        np.testing.assert_allclose(got, full)

    def test_scatter_dimension_rows(self, comm):
        p = comm.size
        x = jnp.arange(p * p * 2, dtype=jnp.float32)

        def body(v):  # (p, 2) per shard; reduce over ranks, scatter rows
            return comm.psum_scatter(v.reshape(p, 2), scatter_dimension=0)

        got = np.asarray(_smap(comm, body)(x)).reshape(p, 2)
        want = np.asarray(x).reshape(p, p, 2).sum(0)
        np.testing.assert_allclose(got, want)


class TestRingShiftWidth:
    @pytest.mark.parametrize("shift", [-2, -1, 0, 1, 2, 5])
    def test_shift_grid(self, comm, shift):
        p = comm.size
        x = jnp.arange(p, dtype=jnp.float32)

        def body(v):
            return comm.ring_shift(v, shift)

        got = np.asarray(_smap(comm, body)(x))
        want = np.roll(np.arange(p), shift)
        np.testing.assert_allclose(got, want)

    def test_composed_shifts_identity(self, comm):
        x = jnp.arange(comm.size, dtype=jnp.float32)

        def body(v):
            return comm.ring_shift(comm.ring_shift(v, 3), -3)

        got = np.asarray(_smap(comm, body)(x))
        np.testing.assert_allclose(got, np.asarray(x))


class TestDtypeSweep:
    @pytest.mark.parametrize(
        "dtype", [jnp.float32, jnp.int32, jnp.uint32, jnp.bfloat16]
    )
    def test_psum_dtypes(self, comm, dtype):
        p = comm.size
        x = jnp.ones(p, dtype)
        got = _smap(comm, lambda v: comm.psum(v))(x)
        assert got.dtype == dtype
        assert float(np.asarray(got.astype(jnp.float32))[0]) == float(p)

    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.int32])
    def test_all_gather_dtypes(self, comm, dtype):
        p = comm.size
        x = jnp.arange(p, dtype=dtype)
        got = _smap(comm, lambda v: comm.all_gather(v))(x)
        assert got.dtype == dtype
        np.testing.assert_array_equal(
            np.asarray(got)[:p].astype(np.int64), np.arange(p)
        )
