"""Per-collective dtype x shape x world-size grid over parallel/comm.py.

The analog of the reference's ``test_communication.py`` (VERDICT item
7): every explicit collective wrapper checked against a numpy model,
swept over dtypes and world sizes — including worlds produced by
``comm.reshape`` (the post-reshape shard layouts of the elastic path)
— and the chunk/lshape/counts-displs metadata swept over uneven
extents that leave ragged true shards under the pad-and-mask canonical
distribution.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import shard_map

import heat_tpu as ht
from heat_tpu.parallel.comm import Communication

#: world sizes: the full test mesh plus two reshaped (surviving) worlds
SIZES = [8, 5, 3]


def _comm(size: int) -> Communication:
    w = ht.get_comm()
    if size == w.size:
        return w
    return w.reshape(size)


def _run_collective(comm, fn, *arrs):
    """Run ``fn`` (collective calls on ``comm``) under shard_map over
    the comm's mesh; each operand's leading axis is the split axis."""
    from jax.sharding import PartitionSpec as P

    spec = P(comm.axis_name)
    prog = jax.jit(
        shard_map(
            fn, mesh=comm.mesh,
            in_specs=(spec,) * len(arrs), out_specs=spec,
        )
    )
    return np.asarray(prog(*[jnp.asarray(a) for a in arrs]))


# ----------------------------------------------------------------------
# metadata: chunk / lshape_map / counts_displs over uneven extents
# ----------------------------------------------------------------------
class TestChunkMetadataGrid:
    @pytest.mark.parametrize("size", SIZES + [1])
    @pytest.mark.parametrize("shape,split", [
        ((13,), 0), ((16,), 0), ((5,), 0),        # uneven / even / fewer rows than devices
        ((13, 4), 0), ((7, 5), 1), ((8, 3), 0),
        ((4, 4), None),
    ])
    def test_partition_is_exact_and_ordered(self, size, shape, split):
        c = _comm(size)
        lm = c.lshape_map(shape, split)
        assert lm.shape == (size, len(shape))
        if split is None:
            assert all(tuple(r) == shape for r in lm)
            return
        # true local shapes tile the extent exactly, high ranks own the
        # (possibly empty) remainder
        assert lm[:, split].sum() == shape[split]
        per = c.padded_extent(shape[split]) // size
        offs, stops = [], []
        for r in range(size):
            off, lsh, slices = c.chunk(shape, split, rank=r)
            assert lsh == tuple(lm[r])
            assert slices[split] == slice(off, off + lsh[split])
            for d, s in enumerate(shape):
                if d != split:
                    assert slices[d] == slice(0, s)
            assert lsh[split] <= per
            offs.append(off)
            stops.append(off + lsh[split])
        assert offs == sorted(offs)
        assert stops[-1] == shape[split]
        counts, displs, local = c.counts_displs_shape(shape, split)
        assert sum(counts) == shape[split]
        assert list(displs) == [int(x) for x in np.cumsum((0,) + counts[:-1])]

    @pytest.mark.parametrize("size", SIZES)
    def test_padding_arithmetic(self, size):
        c = _comm(size)
        for extent in range(1, 3 * size + 2):
            assert c.padded_extent(extent) % size == 0
            assert 0 <= c.pad_amount(extent) < size
            assert c.padded_extent(extent) - c.pad_amount(extent) == extent


# ----------------------------------------------------------------------
# data ops on reshaped worlds with uneven shards
# ----------------------------------------------------------------------
class TestRaggedDataOnReshapedWorlds:
    @pytest.mark.parametrize("size", SIZES)
    @pytest.mark.parametrize("dtype", [np.float32, np.float64, np.int32])
    @pytest.mark.parametrize("extent", [13, 16, 5])
    def test_reductions_match_numpy(self, size, dtype, extent):
        c = _comm(size)
        vals = (np.arange(extent * 3) % 17).astype(dtype).reshape(extent, 3)
        x = ht.array(vals, split=0, comm=c)
        assert float(x.sum()) == float(vals.sum())
        assert float(x.max()) == float(vals.max())
        assert float(x.min()) == float(vals.min())
        assert np.allclose(x.numpy(), vals)

    @pytest.mark.parametrize("size", SIZES)
    def test_matmul_across_split(self, size):
        c = _comm(size)
        a = np.arange(13 * 4, dtype=np.float64).reshape(13, 4)
        b = np.arange(4 * 2, dtype=np.float64).reshape(4, 2)
        out = ht.array(a, split=0, comm=c) @ ht.array(b, comm=c)
        assert np.allclose(out.numpy(), a @ b)


# ----------------------------------------------------------------------
# explicit collectives vs numpy models
# ----------------------------------------------------------------------
DTYPES = [np.float32, np.int32, np.float64]


class TestCollectiveGrid:
    @pytest.mark.parametrize("size", SIZES)
    @pytest.mark.parametrize("dtype", DTYPES)
    @pytest.mark.parametrize("op", ["psum", "pmax", "pmin"])
    def test_reductions(self, size, dtype, op):
        c = _comm(size)
        vals = ((np.arange(size * 2) * 7) % 23 - 5).astype(dtype)
        out = _run_collective(c, getattr(c, op), vals)
        model = {
            "psum": lambda v: v.reshape(size, -1).sum(0),
            "pmax": lambda v: v.reshape(size, -1).max(0),
            "pmin": lambda v: v.reshape(size, -1).min(0),
        }[op](vals)
        # result is replicated per shard -> concatenated back: tile
        assert np.array_equal(out, np.tile(model, size))

    @pytest.mark.parametrize("size", SIZES)
    @pytest.mark.parametrize("dtype", [np.float32, np.int32])
    def test_all_gather_tiled(self, size, dtype):
        c = _comm(size)
        vals = np.arange(size * 3, dtype=dtype)
        out = _run_collective(c, lambda v: c.all_gather(v), vals)
        # tiled gather of each 3-row shard -> every shard holds the full
        # vector; shard_map concatenates the replicas
        assert np.array_equal(out, np.tile(vals, size))

    @pytest.mark.parametrize("size", SIZES)
    @pytest.mark.parametrize("dtype", [np.float32, np.float64])
    def test_psum_scatter(self, size, dtype):
        c = _comm(size)
        vals = np.arange(size * size, dtype=dtype)
        out = _run_collective(c, lambda v: c.psum_scatter(v), vals)
        assert np.allclose(out, vals.reshape(size, size).sum(0))

    @pytest.mark.parametrize("size", SIZES)
    @pytest.mark.parametrize("dtype", [np.float32, np.int32])
    def test_all_to_all(self, size, dtype):
        c = _comm(size)
        # (size*size) rows: shard r holds rows [r*size, (r+1)*size);
        # all_to_all(split 0, concat 0) transposes the block matrix
        vals = np.arange(size * size, dtype=dtype)
        out = _run_collective(c, lambda v: c.all_to_all(v, 0, 0), vals)
        want = vals.reshape(size, size).T.reshape(-1)
        assert np.array_equal(out, want)

    @pytest.mark.parametrize("size", SIZES)
    @pytest.mark.parametrize("dtype", [np.int32, np.float32])
    def test_exscan_and_pscan(self, size, dtype):
        c = _comm(size)
        counts = (np.arange(size) + 1).astype(dtype)
        ex = _run_collective(c, lambda v: c.exscan(v), counts)
        assert np.array_equal(ex, np.concatenate([[0], np.cumsum(counts)[:-1]]).astype(dtype))
        inc = _run_collective(c, lambda v: c.pscan(v), counts)
        assert np.array_equal(inc, np.cumsum(counts).astype(dtype))

    @pytest.mark.parametrize("size", SIZES)
    def test_ring_shift_and_ppermute(self, size):
        c = _comm(size)
        vals = np.arange(size, dtype=np.float32)
        out = _run_collective(c, lambda v: c.ring_shift(v, 1), vals)
        want = np.roll(vals, 1)
        assert np.array_equal(out, want)
        perm = [(i, (i + 2) % size) for i in range(size)]
        out2 = _run_collective(c, lambda v: c.ppermute(v, perm), vals)
        assert np.array_equal(out2, np.roll(vals, 2))

    @pytest.mark.parametrize("size", SIZES)
    def test_axis_index(self, size):
        c = _comm(size)
        vals = np.zeros(size, dtype=np.int32)
        out = _run_collective(
            c, lambda v: v + c.axis_index(c.axis_name).astype(jnp.int32), vals
        )
        assert np.array_equal(out, np.arange(size, dtype=np.int32))


# ----------------------------------------------------------------------
# comm-volume accounting stays live on reshaped comms
# ----------------------------------------------------------------------
class TestAccountingOnReshapedComms:
    def test_collective_counters_increment(self):
        from heat_tpu.telemetry import metrics as tm

        c = _comm(3)
        before = tm.counter("comm.calls.psum").value
        vals = np.ones(3, dtype=np.float32)
        _run_collective(c, c.psum, vals)
        assert tm.counter("comm.calls.psum").value >= before + 1
