"""``statistics.percentile`` / ``median`` along one long axis: exact selection
by counting passes, no sorted copy (PR 33).

The select route is held to ``numpy.percentile`` in float64 (every
``interpolation`` kind, several ``q`` at once, both ends, every axis, split
and not) and, where numpy and ``jax.numpy`` differ (infinities under a zero
weight, the tie of ``nearest``), to ``jnp.percentile``, whose rule it keeps.
The route is decided from the extent along the axis; the tests steer the
threshold down so that small arrays take it.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import heat_tpu as ht
from heat_tpu import telemetry
from heat_tpu.core import statistics

KINDS = ("linear", "lower", "higher", "midpoint", "nearest")


@pytest.fixture()
def select_from_8(monkeypatch):
    monkeypatch.setattr(statistics, "_SELECT_MIN_EXTENT", 8)


def _route_of(call):
    """The ``route`` of the ``statistics.quantiles`` span the call leaves."""
    prev = telemetry.set_tracing(True)
    try:
        telemetry.clear_spans()
        out = call()
        spans = [r for r in telemetry.get_spans() if r.name == "statistics.quantiles"]
    finally:
        telemetry.set_tracing(prev)
        telemetry.clear_spans()
    return out, [r.attrs for r in spans]


def _values(dtype=np.float32):
    """Negative values, duplicates, a tie across the quartiles, both zeros,
    both infinities; odd rows, so that the quartiles interpolate."""
    rng = np.random.default_rng(3)
    a = (rng.standard_normal((1003, 7)) * [1, 10, 0.1, 1, 1, 1, 100] - [0, 5, 0, 0, 0, 0, 0]).astype(dtype)
    a[:, 1] = np.round(a[:, 1])          # duplicates
    a[:600, 3] = 1.5                     # a tie that holds a quartile and the median
    a[10:20, 4], a[20:30, 4] = 0.0, -0.0
    a[5, 5], a[7, 5], a[9, 5] = np.inf, -np.inf, np.inf
    return a


@pytest.mark.parametrize("split", [None, 0, 1])
@pytest.mark.parametrize("kind", KINDS)
def test_selection_against_numpy(select_from_8, kind, split):
    """Several ``q`` at once, ``q`` 0 and 100, along both axes of a 2-D array.
    ``nearest`` is compared where no rank ties at a half (numpy rounds such a
    tie to even, ``jnp`` down); column 5 holds infinities and is compared
    with ``jnp.percentile`` below."""
    a = _values()
    x = ht.array(a, split=split)
    q = [0.0, 12.5, 33.3, 50.0, 99.9, 100.0] if kind == "nearest" else [0.0, 25.0, 50.0, 75.0, 99.9, 100.0]
    for axis in (0, 1):
        keep = [c for c in range(7) if c != 5] if axis == 0 else slice(None)
        b = a[:, keep] if axis == 0 else a[:, :5]
        y = ht.array(b, split=split)
        (got, spans) = _route_of(lambda: ht.percentile(y, q, axis=axis, interpolation=kind))
        assert [s["route"] for s in spans] == ["select" if b.shape[axis] >= 8 else "sort"]
        want = np.percentile(b.astype(np.float64), q, axis=axis, method=kind)
        assert got.shape == want.shape and got.dtype == ht.float32
        np.testing.assert_allclose(got.numpy(), want, rtol=1e-6, atol=1e-6)
    assert x.shape == a.shape


@pytest.mark.parametrize("kind", KINDS)
@pytest.mark.parametrize("split", [None, 0])
def test_special_values_as_jnp_percentile(select_from_8, kind, split):
    """NaN, the zeros, infinities and ties give what ``jnp.percentile`` gives,
    bit for bit where it gives a number."""
    a = _values()
    a[3, 6] = np.nan  # a NaN anywhere makes the column's quantiles NaN
    q = np.asarray([0.0, 25.0, 50.0, 75.0, 100.0])
    got = ht.percentile(ht.array(a, split=split), q, axis=0, interpolation=kind).numpy()
    want = np.asarray(jnp.percentile(jnp.asarray(a), q, axis=0, method=kind))
    assert np.all(np.isnan(got[:, 6])) and np.array_equal(got, want, equal_nan=True)


@pytest.mark.parametrize("split", [None, 0, 1, 2])
@pytest.mark.parametrize("axis", [0, 1, 2])
def test_an_axis_of_a_3d_array(select_from_8, axis, split):
    b = np.random.default_rng(9).standard_normal((40, 9, 33))
    got, spans = _route_of(lambda: ht.percentile(ht.array(b, split=split), [10.0, 50.0], axis=axis, keepdims=True))
    assert spans[0]["route"] == "select" and spans[0]["passes"] == 33  # float64: 64 bits, 2 a pass, and the neighbours'
    np.testing.assert_allclose(got.numpy(), np.percentile(b, [10.0, 50.0], axis=axis, keepdims=True), rtol=1e-12)


@pytest.mark.parametrize("dtype", ["float32", "float64", "bfloat16", "int32"])
def test_input_types(select_from_8, dtype):
    """Narrow floats select on their float32 image (exact), integers are cast
    as they were before, float64 takes a 64-bit key."""
    rng = np.random.default_rng(1)
    a = rng.integers(-50, 50, (257, 3)) if dtype == "int32" else rng.standard_normal((257, 3))
    x = ht.array(a, split=0).astype(getattr(ht, dtype))
    got = ht.median(x, axis=0).numpy().astype(np.float64)
    want = np.median(np.asarray(x.numpy(), np.float64), axis=0)
    np.testing.assert_allclose(got, want, rtol=1e-2 if dtype == "bfloat16" else 1e-6, atol=1e-6)


@pytest.mark.parametrize("q,passes", [(50.0, 11), ([25.0, 75.0], 17), ([25.0, 50.0, 75.0], 17), ([10, 20, 30, 40, 50], 33),
                                       ([0.0, 100.0], 16)])
def test_all_q_in_one_computation(select_from_8, q, passes):
    """One span, one launch, however many ``q``; the passes follow the number
    of ranks (3 bits a pass for one rank, 2 for up to four, then 1), plus one
    for the upper neighbours where a rank interpolates."""
    x = ht.array(np.random.default_rng(2).standard_normal((1003, 4)).astype(np.float32), split=0)
    got, spans = _route_of(lambda: ht.percentile(x, q, axis=0))
    assert len(spans) == 1 and spans[0]["launches"] == 1 and spans[0]["passes"] == passes
    np.testing.assert_allclose(got.numpy(), np.percentile(x.numpy().astype(np.float64), q, axis=0), rtol=1e-6, atol=1e-6)


def test_robust_scaler_asks_once(select_from_8):
    x = ht.array(np.random.default_rng(4).standard_normal((200, 5)).astype(np.float32), split=0)
    _, spans = _route_of(lambda: ht.preprocessing.RobustScaler().fit(x))
    assert len(spans) == 1 and spans[0]["q"] == (50.0, 25.0, 75.0)


@pytest.mark.parametrize("case", ["short_axis", "flattened", "two_axes", "sketched"])
def test_the_sort_route_returns_what_it_returned(case):
    """Below the threshold, over several axes, flattened or sketched, the
    call is ``jnp.percentile`` as before, result for result."""
    a = np.random.default_rng(6).standard_normal((300, 6)).astype(np.float32)
    x = ht.array(a, split=0)
    kwargs = {"short_axis": dict(axis=0), "flattened": dict(axis=None), "two_axes": dict(axis=(0, 1)),
              "sketched": dict(axis=0, sketched=True, sketch_size=300)}[case]
    got, spans = _route_of(lambda: ht.percentile(x, [30.0, 60.0], **kwargs))
    assert [s["route"] for s in spans] == ["sort"]
    if case != "sketched":
        want = jnp.percentile(jnp.asarray(a), jnp.asarray([30.0, 60.0]), axis=kwargs["axis"])
        assert np.array_equal(got.numpy(), np.asarray(want))
    assert statistics._SELECT_MIN_EXTENT == 1 << 14


def test_a_long_axis_selects_by_itself():
    """No steering: from 2^14 elements on the route is ``select``."""
    a = np.random.default_rng(8).standard_normal((1 << 14, 2)).astype(np.float32)
    got, spans = _route_of(lambda: ht.percentile(ht.array(a, split=0), [25.0, 50.0, 75.0], axis=0))
    assert spans[0]["route"] == "select" and spans[0]["passes"] == 17
    np.testing.assert_allclose(got.numpy(), np.percentile(a.astype(np.float64), [25, 50, 75], axis=0), rtol=1e-6, atol=1e-7)


def test_out_of_range_q_still_raises():
    x = ht.array(np.zeros((20000, 2), np.float32), split=0)
    with pytest.raises(ValueError):
        ht.percentile(x, 101.0, axis=0)
    with pytest.raises(ValueError, match="interpolation"):
        ht.percentile(x, 50.0, axis=0, interpolation="cubic")
    assert np.array_equal(ht.percentile(x, 50.0, axis=0).numpy(), np.zeros(2, np.float32))


# ------------------------------------------------------ by group, ranks on the device
GROUPS = 4


def _grouped_values(dtype):
    """Five columns under four labels: duplicates, a tie that holds a group's
    middle, both zeros, both infinities, negative values; group 2 small,
    label 4 a row that belongs to no group."""
    rng = np.random.default_rng(12)
    a = (rng.standard_normal((211, 5)) * [1, 10, 0.1, 1, 100]).astype(dtype)
    a[:, 1] = np.round(a[:, 1])
    a[:90, 3] = 1.5
    a[10:20, 2], a[20:30, 2] = 0.0, -0.0
    a[5, 4], a[7, 4] = np.inf, -np.inf
    labels = rng.integers(0, GROUPS, 211).astype(np.int32)
    labels[labels == 2] = np.where(rng.random((labels == 2).sum()) < 0.8, 0, 2)
    labels[::37] = GROUPS
    return a, labels


def _packed(a, labels):
    """The table as the grouped selection takes it: `kernels.pack_columns`'
    copy, and the labels in one column's shape, no group's behind the last
    row (``GROUPS`` names none of ``GROUPS`` groups, nor of fewer)."""
    from heat_tpu.core import kernels

    cols = kernels.pack_columns(jnp.asarray(a))
    behind = cols.shape[1] * cols.shape[2] - len(labels)
    return cols, jnp.pad(jnp.asarray(labels), (0, behind), constant_values=GROUPS).reshape(cols.shape[1:])


def _middle(sizes):
    """The rank of numpy's median (its lower one where the size is even)."""
    return (jnp.maximum(sizes, 1) - 1) // 2


@functools.partial(jax.jit, static_argnames=("bits", "groups"))
def _grouped(cols, labels, ranks, bits, groups=GROUPS):
    """(low, high, nans, sizes), each groups x columns; ``ranks`` groups x
    columns or groups x 1, or None: the selection then makes each group's
    middle rank of the size it has counted."""
    real = statistics._GROUP_BITS
    statistics._GROUP_BITS = bits
    try:
        found = statistics._select_ranks(cols, (1, 2), _middle if ranks is None else ranks[:, :, None, None], True, None,
                                         lambda v: v, lambda v: v, group=(labels, groups))
        return tuple(v[:, :, 0, 0] for v in found)
    finally:
        statistics._GROUP_BITS = real


@pytest.mark.parametrize("bits", [1, 2])
@pytest.mark.parametrize("dtype", ["float32", "float64"])
def test_every_groups_every_rank_bit_for_bit(bits, dtype):
    """``_select_ranks`` with a group and ranks that are device values: for
    every group, every rank and its upper neighbour are the elements
    ``np.sort`` of the group's members puts there, to the bit but for a zero's
    sign (``np.sort`` leaves -0.0 and 0.0 as they came; the key puts -0.0
    first), at 1 and 2 bits a pass (what the counting kernel packs into a
    lane).  One compiled program serves
    every rank: they are its arguments."""
    a, labels = _grouped_values(dtype)
    members = [np.sort(a[labels == g], axis=0) for g in range(GROUPS)]
    bits_of = lambda v: (np.asarray(v) + 0.0).view(np.int32 if dtype == "float32" else np.int64)  # noqa: E731
    x, lab = _packed(a, labels)
    for r in range(max(len(m) for m in members)):
        ranks = np.asarray([min(r, len(m) - 1) for m in members], np.int32)
        low, high, nans, sizes = _grouped(x, lab, jnp.asarray(ranks)[:, None], bits=bits)
        assert low.shape == high.shape == nans.shape == sizes.shape == (GROUPS, 5) and not np.asarray(nans).any()
        assert np.array_equal(np.asarray(sizes), np.repeat([[len(m)] for m in members], 5, axis=1))
        for g, m in enumerate(members):
            assert np.array_equal(bits_of(low[g]), bits_of(m[ranks[g]])), (g, r)
            if ranks[g] + 1 < len(m):  # the largest member has no neighbour, and nothing reads one
                assert np.array_equal(bits_of(high[g]), bits_of(m[ranks[g] + 1])), (g, r)


def test_ranks_of_a_group_may_differ_by_column():
    a, labels = _grouped_values("float32")
    members = [np.sort(a[labels == g], axis=0) for g in range(GROUPS)]
    ranks = np.random.default_rng(3).integers(0, [[len(m)] for m in members], (GROUPS, 5)).astype(np.int32)
    low, _, _, _ = _grouped(*_packed(a, labels), jnp.asarray(ranks), bits=2)
    want = np.stack([m[ranks[g], np.arange(5)] for g, m in enumerate(members)])
    assert np.array_equal(np.asarray(low), want)


def test_nans_are_counted_by_group_and_an_empty_group_harms_no_other():
    a, labels = _grouped_values("float32")
    a[np.flatnonzero(labels == 0)[:3], 2] = np.nan
    a[np.flatnonzero(labels == 3)[0], 4] = -np.nan
    labels[labels == 2] = GROUPS  # group 2 is empty
    want = np.zeros((GROUPS, 5), np.int32)  # 1 where a NaN is among the members, however many (their count until PR 38)
    want[0, 2], want[3, 4] = 1, 1
    ranks = jnp.asarray([(np.sum(labels == g) - 1) // 2 if np.any(labels == g) else 0 for g in range(GROUPS)], jnp.int32)
    low, high, nans, sizes = _grouped(*_packed(a, labels), ranks[:, None], bits=2)
    assert np.array_equal(np.asarray(nans), want)
    assert np.array_equal(np.asarray(sizes)[:, 0], [np.sum(labels == g) for g in range(GROUPS)])  # the NaNs are members too
    for g in (0, 1, 3):
        clean = [c for c in range(5) if not want[g, c]]
        m = np.sort(a[labels == g], axis=0)
        assert np.array_equal(np.asarray(low)[g, clean], m[int(ranks[g])][clean])


def test_the_ungrouped_selection_is_the_case_of_no_group():
    """The static ranks of ``percentile`` on the table as it lies, and the
    same ranks as one group's device values on its packed copy (the counts
    then the kernel's), give the same elements."""
    a, _ = _grouped_values("float32")
    static = jax.jit(lambda x: statistics._select_ranks(x, 0, (52, 105), True, None, lambda v: v, lambda v: v))(jnp.asarray(a))
    cols, one_group = _packed(a, np.zeros(len(a), np.int32))
    for i, r in enumerate((52, 105)):
        low, high, _, _ = _grouped(cols, one_group, jnp.full((1, 1), r, jnp.int32), bits=2, groups=1)
        assert np.array_equal(np.asarray(low[0]), np.asarray(static[0][i, 0]))
        assert np.array_equal(np.asarray(high[0]), np.asarray(static[1][i, 0]))


@pytest.mark.parametrize("rows", [1000, 140000], ids=["one_block", "two_blocks"])
@pytest.mark.parametrize("dtype", ["float32", "float64"])
def test_the_counting_kernel_counts_digits_by_group(dtype, rows):
    """``kernels.grouped_digit_counts`` (through the interpreter here): for
    every group and column, how many of the group's keys hold each digit,
    among those that agree with the group's settled bits above it; the first
    pass (no bit above), a middle one, the last, and one bit a pass; rows
    under a label that names no group, and the zeros behind the last row,
    count nowhere."""
    from heat_tpu.core import kernels

    rng = np.random.default_rng(0)
    a = (rng.standard_normal((rows, 3)) * [1, 100, 0.01]).astype(dtype)
    a[5, 0], a[7, 1] = -0.0, np.inf
    lab = rng.integers(0, GROUPS + 1, rows).astype(np.int32)
    cols, labels = _packed(a, lab)
    assert cols.shape == (3, 256 * -(-rows // (256 * 512)), 512) and np.array_equal(np.asarray(cols).reshape(3, -1)[:, :rows], a.T)
    key = np.asarray(statistics._offset(statistics._order_key(jnp.asarray(a))))
    nb, u = key.dtype.itemsize * 8, key.dtype.type
    settled = np.stack([key[np.flatnonzero(lab == g)[0]] for g in range(GROUPS)])  # some member's key, a group
    for shift, bits in ((nb - 2, 2), (nb - 4, 2), (10, 2), (0, 2), (7, 1)):
        above = u(shift + bits)
        prefix = settled >> above << above if shift + bits < nb else np.zeros_like(settled)
        got = np.asarray(kernels.grouped_digit_counts(cols, labels, jnp.asarray(prefix), shift, bits, GROUPS))
        want = np.zeros((GROUPS, 1 << bits, 3), np.int64)
        for g in range(GROUPS):
            for c in range(3):
                mine = key[lab == g, c]
                if shift + bits < nb:
                    mine = mine[mine >> above == prefix[g, c] >> above]
                want[g, :, c] = np.bincount(((mine >> u(shift)) & u((1 << bits) - 1)).astype(np.int64), minlength=1 << bits)
        assert np.array_equal(got, want), (shift, bits)


def _keys(a):
    """The signed order key of every value, as numpy integers."""
    return np.asarray(statistics._order_key(jnp.asarray(a)))


@pytest.mark.parametrize("groups,rows", [(1, 1000), (3, 1000), (4, 1000), (4, 140000)],
                         ids=["one_group", "three_groups", "four_groups", "four_groups_two_blocks"])
@pytest.mark.parametrize("dtype", ["float32", "float64"])
def test_the_neighbours_kernel_finds_the_next_key_by_group(dtype, groups, rows):
    """``kernels.grouped_neighbours`` (through the interpreter here): for
    every group and column, over the group's members alone, the smallest key
    strictly above the group's pivot, or the key type's smallest value, no
    number's key, where a NaN is among them.  The pivots
    by column: 0, a key that members tie at (the ties are not above it); 1,
    the group's largest key (nothing lies above: the key type's largest
    value); 2, the key of -0.0 (+0.0 lies above it, and compares equal to it
    as a float).  The last group is empty; column 0 holds a NaN of either
    sign, +inf and -inf among the first group's members; rows under a label
    outside ``range(groups)``, and the zeros behind the last row, belong to
    no group."""
    from heat_tpu.core import kernels

    rng = np.random.default_rng(5)
    a = np.round(rng.standard_normal((rows, 3)) * [4, 100, 2]).astype(dtype)  # whole numbers: ties everywhere
    lab = rng.integers(-1, groups + 2, rows).astype(np.int32)  # -1, groups and groups + 1 name no group
    if groups > 1:
        lab[lab == groups - 1] = -1  # the last group is empty
    first = np.flatnonzero(lab == 0)
    a[first[:5], 0] = [np.nan, -np.nan, np.inf, -np.inf, np.inf]
    a[first[5:9], 2] = [-0.0, 0.0, -0.0, 0.0]
    a[np.flatnonzero(lab == -1)[:2], 1] = [np.nan, 1e30]  # no group's NaN, no group's largest
    key = _keys(a)
    top = np.iinfo(key.dtype).max
    pivots = np.zeros((groups, 3), key.dtype)
    for g in range(groups):
        mine = key[lab == g]
        if len(mine):
            pivots[g] = [np.sort(mine[:, 0])[len(mine) // 2], mine[:, 1].max(), _keys(np.asarray(-0.0, dtype))]
    above = kernels.grouped_neighbours(*_packed(a, lab), jnp.asarray(pivots), groups)
    assert above.shape == (groups, 3) and above.dtype == key.dtype
    for g in range(groups):
        for c in range(3):
            mine = key[lab == g, c]
            want = np.iinfo(key.dtype).min if np.isnan(a[lab == g, c]).any() else min(mine[mine > pivots[g, c]], default=top)
            assert int(above[g, c]) == want, (g, c)
    assert int(above[0, 0]) == np.iinfo(key.dtype).min and int(above[0, 1]) == top and int(above[0, 2]) == _keys(np.asarray(0.0, dtype))
    a[first[:2], 0] = -1e30  # without the NaNs: the infinities are numbers, +inf the group's largest
    pivots[0, 0] = _keys(np.asarray(1e30, dtype))
    above = kernels.grouped_neighbours(*_packed(a, lab), jnp.asarray(pivots), groups)
    assert int(above[0, 0]) == _keys(np.asarray(np.inf, dtype))
    if groups > 1:
        assert np.all(np.asarray(above)[-1] == top)


@pytest.mark.parametrize("groups", [1, 3, 4])
@pytest.mark.parametrize("dtype", ["float32", "float64"])
def test_the_sizes_counted_inside_give_numpys_median(dtype, groups):
    """``_select_ranks`` with a group and the ranks as a function of the
    sizes: the first counting pass's totals are the groups' sizes, the last
    one's running count says whether ties at the median reach the next rank
    (column 3: they do; column 1, whole numbers: some do; the others: none
    does), and the rule of ``KMedians._medians`` on what comes back is
    ``numpy.median`` of each group's members.  A group of one member has
    nothing above its median; the last group is empty."""
    a, _ = _grouped_values(dtype)
    rng = np.random.default_rng(21)
    labels = rng.integers(0, max(groups - 1, 1), len(a)).astype(np.int32)
    labels[::37] = groups + 3  # no group's
    if groups > 1:
        labels[labels == groups - 2] = groups  # leave the group before the last ...
        labels[11] = groups - 2  # ... one member
    cols, lab = _packed(a, labels)
    low, high, nans, sizes = (np.asarray(v) for v in _grouped(cols, lab, None, bits=2, groups=groups))
    assert not nans.any()
    for g in range(groups):
        mine = a[labels == g]
        assert np.all(sizes[g] == len(mine))
        if len(mine):
            got = np.where(len(mine) % 2 == 0, 0.5 * (low[g] + high[g]), low[g])
            assert np.array_equal(got, np.median(mine, axis=0)), g
    if groups > 1:
        assert np.array_equal(low[groups - 2], a[11]) and not sizes[-1].any()
    # the same elements as with the ranks handed over, as before the sizes were counted inside
    ranks = jnp.asarray([(max((labels == g).sum(), 1) - 1) // 2 for g in range(groups)], jnp.int32)[:, None]
    given = _grouped(cols, lab, ranks, bits=2, groups=groups)
    filled = sizes[:, 0] > 1  # a single member has no upper neighbour, an empty group no member
    assert np.array_equal(np.asarray(given[0])[filled], low[filled]) and np.array_equal(np.asarray(given[1])[filled], high[filled])


def test_a_nan_among_the_members_is_that_groups_alone():
    """Through ``KMedians._medians``, sizes and ranks made inside: the
    cluster with a NaN member gets NaN in that column, an empty cluster keeps
    its center, and labels of zeros everywhere make every place a member of
    cluster 0 (the benchmark's fault ``unmasked`` is planted on that)."""
    from heat_tpu.cluster import kmedians

    a, labels = _grouped_values("float32")
    labels[labels == 2] = GROUPS  # cluster 2 is empty
    a[np.flatnonzero(labels == 1)[4], 3] = np.nan
    cols, lab = _packed(a, labels)
    centers = jnp.asarray(np.arange(GROUPS * 5, dtype=np.float32).reshape(GROUPS, 5))
    got = np.asarray(jax.jit(lambda *v: kmedians._medians(*v, lambda s: s, lambda s: s))(cols, lab, centers))
    want = np.stack([np.median(a[labels == g], axis=0) if g != 2 else np.asarray(centers[2]) for g in range(GROUPS)])
    assert np.isnan(got[1, 3]) and np.isnan(want[1, 3]) and np.array_equal(got, want, equal_nan=True)
    of_all = np.asarray(jax.jit(lambda *v: kmedians._medians(*v, lambda s: s, lambda s: s))(cols, jnp.zeros_like(lab), centers))
    every_place = np.concatenate([a, np.zeros((lab.size - len(a), 5), np.float32)])
    assert np.array_equal(of_all[0], np.median(every_place, axis=0), equal_nan=True) and np.array_equal(of_all[1:], np.asarray(centers)[1:])
