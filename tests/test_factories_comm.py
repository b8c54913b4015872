"""Factory and communication-layer tests (reference: heat/core/tests/
test_factories.py 1108 LoC, test_communication.py 2494 LoC).  The comm
tests target the mesh facade: chunk math, counts/displs, sub-communication,
and the collective wrappers under shard_map."""

import numpy as np
import pytest

import heat_tpu as ht


# ---------------------------------------------------------------- factories


@pytest.mark.parametrize("split", [None, 0])
def test_arange_variants(ht, split):
    np.testing.assert_allclose(ht.arange(7, split=split).numpy(), np.arange(7))
    np.testing.assert_allclose(ht.arange(2, 11, split=split).numpy(), np.arange(2, 11))
    np.testing.assert_allclose(ht.arange(1, 10, 2, split=split).numpy(), np.arange(1, 10, 2))
    np.testing.assert_allclose(
        ht.arange(0.0, 1.0, 0.25, split=split).numpy(), np.arange(0.0, 1.0, 0.25)
    )


@pytest.mark.parametrize("split", [None, 0, 1])
def test_zeros_ones_empty_full(ht, split):
    for fac, npfac in ((ht.zeros, np.zeros), (ht.ones, np.ones)):
        a = fac((5, 6), dtype=ht.float32, split=split)
        np.testing.assert_allclose(a.numpy(), npfac((5, 6), np.float32))
    f = ht.full((5, 6), 3.5, split=split)
    np.testing.assert_allclose(f.numpy(), np.full((5, 6), 3.5))
    e = ht.empty((5, 6), split=split)
    assert e.shape == (5, 6)


def test_like_factories(ht):
    a = ht.arange(12, dtype=ht.float32, split=0).reshape((3, 4))
    for fac, want in (
        (ht.zeros_like, np.zeros((3, 4))),
        (ht.ones_like, np.ones((3, 4))),
    ):
        b = fac(a)
        assert b.split == a.split and b.dtype == a.dtype
        np.testing.assert_allclose(b.numpy(), want)
    c = ht.full_like(a, 9.0)
    np.testing.assert_allclose(c.numpy(), np.full((3, 4), 9.0))
    d = ht.empty_like(a)
    assert d.shape == (3, 4) and d.split == 0


def test_eye_identity(ht):
    np.testing.assert_allclose(ht.eye(5, split=0).numpy(), np.eye(5))
    np.testing.assert_allclose(ht.eye((4, 6), split=1).numpy(), np.eye(4, 6))
    np.testing.assert_allclose(ht.identity(3).numpy(), np.identity(3))


@pytest.mark.parametrize("num,endpoint", [(7, True), (10, False), (1, True)])
def test_linspace_logspace_geomspace(ht, num, endpoint):
    np.testing.assert_allclose(
        ht.linspace(-2.0, 3.0, num, endpoint=endpoint, split=0).numpy(),
        np.linspace(-2.0, 3.0, num, endpoint=endpoint),
        rtol=1e-6,
        atol=1e-6,
    )
    np.testing.assert_allclose(
        ht.logspace(0.0, 2.0, num, endpoint=endpoint).numpy(),
        np.logspace(0.0, 2.0, num, endpoint=endpoint),
        rtol=1e-5,
    )
    if num > 1 or endpoint:
        np.testing.assert_allclose(
            ht.geomspace(1.0, 100.0, num, endpoint=endpoint).numpy(),
            np.geomspace(1.0, 100.0, num, endpoint=endpoint),
            rtol=1e-5,
        )


def test_meshgrid(ht):
    x = ht.arange(4, split=0)
    y = ht.arange(3)
    gx, gy = ht.meshgrid(x, y)
    nx, ny = np.meshgrid(np.arange(4), np.arange(3))
    np.testing.assert_allclose(gx.numpy(), nx)
    np.testing.assert_allclose(gy.numpy(), ny)


def test_array_copy_and_dtype_inference(ht):
    src = np.array([[1, 2], [3, 4]], np.int64)
    a = ht.array(src)
    assert a.dtype in (ht.int64, ht.int32)
    b = ht.array([1.0, 2.5])
    assert b.dtype in (ht.float32, ht.float64)
    c = ht.array(a)  # from DNDarray
    np.testing.assert_allclose(c.numpy(), src)
    d = ht.asarray(src)
    np.testing.assert_allclose(d.numpy(), src)


def test_array_is_split_ingestion(ht):
    # single-controller semantics: the passed array is this process's
    # pre-distributed data (the whole array on one host); it is wrapped
    # in place with the declared split, no reshard
    local = np.arange(6.0).reshape(2, 3)
    a = ht.array(local, is_split=0)
    assert a.split == 0
    np.testing.assert_allclose(a.numpy(), local)
    with pytest.raises(ValueError):
        ht.array(local, split=0, is_split=0)  # mutually exclusive


def test_from_partition_dict_roundtrip(ht):
    a = ht.arange(20, dtype=ht.float32, split=0).reshape((10, 2))
    parts = a.__partitioned__
    b = ht.from_partition_dict(parts)
    np.testing.assert_allclose(b.numpy(), a.numpy())


# ----------------------------------------------------------- communication


def test_chunk_covers_extent(ht):
    comm = ht.get_comm()
    for extent in (1, 7, 8, 13, 64):
        total = 0
        prev_stop = 0
        for r in range(comm.size):
            off, lshape, slices = comm.chunk((extent, 3), 0, rank=r)
            assert off == prev_stop or lshape[0] == 0
            total += lshape[0]
            prev_stop = off + lshape[0] if lshape[0] else prev_stop
        assert total == extent


def test_chunk_split_none_replicates(ht):
    comm = ht.get_comm()
    off, lshape, slices = comm.chunk((5, 4), None)
    assert off == 0 and lshape == (5, 4)


def test_counts_displs(ht):
    comm = ht.get_comm()
    counts, displs, shape = comm.counts_displs_shape((13, 2), 0)
    assert sum(counts) == 13
    assert displs[0] == 0
    for i in range(1, len(displs)):
        assert displs[i] == displs[i - 1] + counts[i - 1]


def test_sub_communication_split(ht):
    comm = ht.get_comm()
    if comm.size < 2:
        pytest.skip("needs >= 2 devices")
    sub = comm.split(list(range(comm.size // 2)))
    assert sub.size == comm.size // 2
    a = ht.arange(6, split=0, comm=sub)
    np.testing.assert_allclose(a.numpy(), np.arange(6))


def test_collective_wrappers(ht):
    """psum/all_gather/ppermute/all_to_all wrappers under shard_map."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from jax import shard_map

    comm = ht.get_comm()
    n = comm.size

    def body(x):
        s = comm.psum(x)
        g = comm.all_gather(x)
        idx = comm.axis_index()
        shifted = comm.ring_shift(x, 1)
        return s, g, shifted + 0 * idx

    x = jnp.arange(float(n)).reshape(n, 1)
    s, g, shifted = jax.jit(
        shard_map(
            body,
            mesh=comm.mesh,
            in_specs=P(comm.axis_name),
            out_specs=(P(comm.axis_name), P(comm.axis_name), P(comm.axis_name)),
            check_vma=False,
        )
    )(x)
    np.testing.assert_allclose(np.asarray(s).ravel(), [np.arange(n).sum()] * n)
    np.testing.assert_allclose(np.asarray(shifted).ravel(), np.roll(np.arange(n), 1))


def test_use_comm_and_sanitize(ht):
    comm = ht.get_comm()
    assert ht.sanitize_comm(None) is ht.get_comm()
    assert ht.sanitize_comm(comm) is comm
    ht.use_comm(comm)
    assert ht.get_comm() is comm
    with pytest.raises((TypeError, ValueError)):
        ht.sanitize_comm("not a comm")


def test_comm_equality_and_repr(ht):
    comm = ht.get_comm()
    assert comm == comm
    assert "Communication" in repr(comm) or "devices" in repr(comm)
    assert comm.is_distributed == (comm.size > 1)


# ----------------------------------------------------- multi-host comm API


def test_process_topology_single_controller(ht):
    comm = ht.get_comm()
    assert comm.process_count == 1
    assert comm.process_rank == 0
    assert comm.local_participants == list(range(comm.size))
    assert len(comm.local_devices) == comm.size


def test_process_chunk_covers_participants(ht):
    comm = ht.get_comm()
    # single process owns every participant: the process block is everything
    off, lshape, slices = comm.process_chunk((13, 4), 0)
    assert off == 0 and lshape == (13, 4)
    off, lshape, _ = comm.process_chunk((13, 4), None)
    assert off == 0 and lshape == (13, 4)
    # a process that owns no participants gets an empty block
    off, lshape, _ = comm.process_chunk((13, 4), 0, process=comm.process_count + 7)
    assert lshape[0] == 0


def test_parallel_init_single_host_noop(ht):
    import heat_tpu

    heat_tpu.parallel.init()  # no coordinator: single-controller no-op
    assert heat_tpu.parallel.is_initialized()
    a = heat_tpu.arange(5, split=0)
    assert float(a.sum()) == 10.0


def test_lazy_import_does_not_touch_backend():
    # regression: importing heat_tpu must not initialize the XLA backend
    # (jax.distributed.initialize would otherwise be impossible after import)
    import subprocess, sys

    code = (
        "import heat_tpu\n"
        "from jax._src import xla_bridge\n"
        "raise SystemExit(1 if xla_bridge._backends else 0)\n"
    )
    import os
    env = {k: v for k, v in os.environ.items() if k not in ("XLA_FLAGS",)}
    r = subprocess.run([sys.executable, "-c", code], env=env, cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    assert r.returncode == 0
