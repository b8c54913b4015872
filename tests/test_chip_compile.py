"""The main path's kernels and steps, compiled at real widths for the chip.

The TPU's compiler is installed where the tests run; it compiles for a
chip that is described (``v5e:2x2``) and not attached.  What it refuses
here — a slice off the tiling, too much fast memory, a program that does
not fit 16 GB, a kernel that cannot be partitioned — it would refuse on
the chip, and costs no chip time to find.  Nothing runs: a compile that
passes is not a chip run.

All cases live in THIS file: the process that describes the topology
holds the TPU library until it exits, so a second file (another xdist
worker) would skip in silence.  The topology is described inside a
module-scoped fixture, never at import, and compiles happen in the
test's own process with the persistent cache off (a compile for a
described chip can be written to the cache but not read back).
"""

import os
import re
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P, SingleDeviceSharding

# __graft_entry__ (the CNN) lives at the repo root
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

#: what one v5e chip offers a program (``memory_stats()["bytes_limit"]``
#: on the chip: 16,909,336,064)
HBM_BYTES = 16_909_336_064


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # lint: allow H501(no TPU compiler here -> skip, never fail)
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def comm4(topo):
    """A 4-device communication over the described chips (its mesh is
    what the program's own shard_maps and shardings are built from)."""
    from heat_tpu.parallel.comm import Communication

    return Communication(list(topo.devices))


@pytest.fixture()
def for_the_chip(monkeypatch):
    """Steer the code that asks ``jax.default_backend()`` (interpret= and
    kernel gates) onto its TPU branch, compile as the chip's process does
    (x64 off: the suite's conftest turns it on, and Mosaic refuses the
    int64 block indices that makes), and keep these compiles out of the
    persistent cache."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cache_was = jax.config.jax_enable_compilation_cache
    x64_was = jax.config.jax_enable_x64
    jax.config.update("jax_enable_compilation_cache", False)
    jax.config.update("jax_enable_x64", False)
    cc.reset_cache()
    try:
        yield
    finally:
        jax.config.update("jax_enable_x64", x64_was)
        jax.config.update("jax_enable_compilation_cache", cache_was)
        cc.reset_cache()


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _device_bytes(compiled) -> int:
    m = compiled.memory_analysis()
    return (
        m.argument_size_in_bytes + m.output_size_in_bytes + m.temp_size_in_bytes
        - m.alias_size_in_bytes
    )


@pytest.mark.parametrize(
    "rows, cols",
    [(2**22, 128), (12_582_912, 128), (2**20, 256), (2**20, 384), (2**20, 512)],
    ids=["4Mx128", "cell_12.6Mx128", "1Mx256", "1Mx384", "1Mx512"],
)
def test_gram_syrk_north_star_shape(one_chip, for_the_chip, rows, cols):
    """Every width ``syrk_supported`` admits, at the tile the width gives
    (``_SYRK_TILE_BYTES``): the kernel and its buffers fit the chip's fast
    memory under the default limit.  Until PR 30 a tile was 2048 rows at
    every width, and 384 and 512 did not compile (RESOURCE_EXHAUSTED in vmem)."""
    from heat_tpu.core import kernels

    assert kernels.syrk_supported(rows, cols, jnp.float32)
    compiled = jax.jit(kernels.gram_syrk).lower(
        _sds((rows, cols), jnp.float32, one_chip)
    ).compile()
    assert "tpu_custom_call" in compiled.as_text()
    assert _device_bytes(compiled) < HBM_BYTES


def test_gram_syrk_takes_a_row_remainder_without_a_copy(one_chip, for_the_chip):
    """Rows that are no multiple of the tile: the kernel's grid stops at
    the last full tile and reads ``x`` itself.  Until PR 30 ``x[:m0]`` stood
    in front of the custom call, a ``slice`` that copied all of it
    (2,147,483,648 B of temporaries at this shape)."""
    from heat_tpu.core import kernels

    rows = 2**22 + 137
    compiled = jax.jit(kernels.gram_syrk).lower(
        _sds((rows, 128), jnp.float32, one_chip)
    ).compile()
    instructions = _entry_instructions(compiled)
    (kernel,) = [i for i in instructions if i[1] == "custom-call"]
    opcode_of = {name: opcode for name, opcode, _, _ in instructions}
    assert kernel[0].startswith("gram_syrk") and [opcode_of[o] for o in kernel[3]] == ["parameter"], kernel
    assert compiled.memory_analysis().temp_size_in_bytes < 2**20


def _entry_instructions(compiled) -> list:
    """[(name, opcode, result shape, operand names)] of the entry computation."""
    entry = re.search(r"^ENTRY [^\n]*\{\n(.*?)^\}", compiled.as_text(), re.S | re.M).group(1)
    found = []
    for line in entry.splitlines():
        m = re.match(r"\s*(?:ROOT )?%([^ ]+) = (.*?) ([a-z][a-z\-]*)\(([^)]*)\)", line)
        if m:
            found.append((m.group(1), m.group(3), re.sub(r"\{[^}]*\}", "", m.group(2)),
                          re.findall(r"%([^ ,)]+)", m.group(4))))
    return found


@pytest.mark.parametrize("trunc", [15, 10], ids=["trunc15", "trunc_eq_k"])
def test_hsvd_rank_program_names_its_kernel_and_makes_two_passes(one_chip, for_the_chip, trunc):
    """The benchmark's cell, 12,582,912 x 128 at rank 10: the trace finds
    the Gram kernel by the name `gram_syrk_ms` reads, and the operations that
    read or write an array of 12,582,912 rows (the passes over A and U) are
    two: the Gram, and A V at the final rank with 1/s fused into its output.
    That fusion is the program's first result (no copy, slice or layout
    change after it) and the program keeps no temporary of U's size.  With
    ``trunc == k`` (``safetyshift=0``, the bypass) the passes are the same."""
    from heat_tpu.core.linalg import svdtools

    rows = 12_582_912
    compiled = svdtools._hsvd_rank_jit.lower(
        _sds((rows, 128), jnp.float32, one_chip), trunc, 1, 2, 10, True, "float32",
        syrk_ok=True, env_cfg=("", "", ""),
    ).compile()
    instructions = _entry_instructions(compiled)
    kernels_found = [name for name, opcode, _, _ in instructions
                     if opcode == "custom-call" and name.startswith("gram_syrk")]
    assert len(kernels_found) == 1, [i[:3] for i in instructions if i[1] == "custom-call"]
    tall = {name for name, _, shape, _ in instructions if str(rows) in shape}
    passes = [(name, opcode, shape) for name, opcode, shape, operands in instructions
              if opcode not in ("parameter", "tuple") and (name in tall or tall & set(operands))]
    assert [p[1:] for p in passes] == [
        ("custom-call", "f32[128,128]"),    # gram_syrk reads A
        ("fusion", f"f32[{rows},10]"),      # A V at rank 10, times 1/s: reads A, writes U once
    ]
    (root,) = [operands for _, opcode, _, operands in instructions if opcode == "tuple"]
    assert root[0] == passes[1][0]
    assert compiled.memory_analysis().temp_size_in_bytes < 2**20
    assert _device_bytes(compiled) < HBM_BYTES


def test_rfft3_leading_512_cubed(one_chip, for_the_chip):
    from heat_tpu.fft import _leading

    compiled = jax.jit(lambda x: _leading.rfft3_leading(x, None)).lower(
        _sds((512, 512, 512), jnp.float32, one_chip)
    ).compile()
    # the stage kernel and the extension kernel
    assert compiled.as_text().count("tpu_custom_call") >= 2
    assert _device_bytes(compiled) < HBM_BYTES


def test_local_flash_attention(one_chip, for_the_chip):
    from heat_tpu.nn import attention

    seq, heads, dim = 8192, 16, 128
    x = _sds((seq, heads, dim), jnp.float32, one_chip)
    compiled = jax.jit(
        lambda q, k, v: attention._local_flash(q, k, v, dim**-0.5, False, seq)
    ).lower(x, x, x).compile()
    assert "tpu_custom_call" in compiled.as_text()
    # far below the einsum path's (heads, seq, seq) f32 score tensor
    assert _device_bytes(compiled) < heads * seq * seq * 4


def test_lloyd_step_one_chip(one_chip, for_the_chip):
    from heat_tpu.cluster import kmeans

    n, f, k = 2**24, 16, 8
    compiled = kmeans._lloyd_step.lower(
        _sds((n, f), jnp.float32, one_chip), _sds((k, f), jnp.float32, one_chip), n_true=n, k=k
    ).compile()
    assert _device_bytes(compiled) < HBM_BYTES


def _computation_instructions(text: str, name: str) -> list:
    """[(name, opcode, result shape, operand names)] of the computation
    called ``name`` in a compiled program's text."""
    block = re.search(r"^%" + re.escape(name) + r" [^\n]*\{\n(.*?)^\}", text, re.S | re.M).group(1)
    found = []
    for line in block.splitlines():
        m = re.match(r"\s*(?:ROOT )?%([^ ]+) = (.*?) ([a-z][a-z\-]*)\(([^)]*)\)", line)
        if m:
            found.append((m.group(1), m.group(3), re.sub(r"\{[^}]*\}", "", m.group(2)),
                          re.findall(r"%([^ ,)]+)", m.group(4))))
    return found


@pytest.mark.parametrize("pad", [0, 3], ids=["every_row_real", "three_pad_rows"])
def test_lloyd_loop_at_the_benchmark_cell(one_chip, for_the_chip, pad):
    """The KMeans cell, 10^8 x 16 points and 8 clusters, 30 iterations: the
    whole fit loop fits the chip beside the resident points (6.4 GB of
    arguments, and the bfloat16 copy, the labels and their minima as
    temporaries: a second copy of the points, or a materialised float32
    product, does not), and one iteration makes TWO passes that read an
    array of 10^8 rows: the assignment (the copy) and the update (copy and
    labels), whose one product carries the counts in its last column.  No
    operation only writes such an array where every row is real; where rows
    are padded (``n_true = n - 3``) one does, the row mask, once an
    iteration, and the update reads it; the counts are no pass of their own
    in either form.  A later PR that adds a pass, or removes one, is seen
    here without a chip."""
    from heat_tpu.cluster import kmeans

    n, f, k = 100_000_000, 16, 8
    compiled = kmeans._lloyd_loop.lower(
        _sds((n, f), jnp.float32, one_chip), _sds((k, f), jnp.float32, one_chip),
        n_true=n - pad, k=k, max_iter=30, tol=-1.0,
    ).compile()
    memory = compiled.memory_analysis()
    assert memory.argument_size_in_bytes < n * f * 4 + 2**20
    assert memory.temp_size_in_bytes < 3_900_000_000  # copy 3.2 GB + labels 0.4 + their minima (and the mask) 0.2
    assert _device_bytes(compiled) < HBM_BYTES
    text = compiled.as_text()
    (body,) = set(re.findall(r" while\([^)]*\), condition=%[^ ,]+, body=%([^ ,]+)", text))
    instructions = _computation_instructions(text, body)
    tall = {name for name, _, shape, _ in instructions if str(n) in shape}
    moving = [(name, opcode, shape, operands) for name, opcode, shape, operands in instructions
              if opcode not in ("parameter", "tuple", "get-tuple-element")]
    readers = [(opcode, shape, len(tall & set(operands))) for _, opcode, shape, operands in moving
               if tall & set(operands)]
    assert readers == [
        ("fusion", f"(bf16[{n}], s32[{n}])", 1),           # lloyd.assign: the one product, the argmin and its minimum
        ("fusion", f"f32[{k},{f + 1}]", 3 if pad else 2),  # lloyd.update: sums and counts; copy, labels (and mask)
    ], readers
    writers_only = [(opcode, shape) for name, opcode, shape, operands in moving
                    if name in tall and not tall & set(operands)]
    assert writers_only == ([("fusion", f"bf16[{n}]")] if pad else []), writers_only  # the row mask, without inputs
    assert text.count("operand_precision={default,high}") == 1  # the assignment's product, as written


def test_lloyd_final_assignment_at_the_benchmark_cell(one_chip, for_the_chip):
    """The fit's last pass at the cell's size: labels for every row and the
    inertia, beside the resident points.  It holds no bfloat16 copy of the
    points: the convert is fused into each of its two products (so the update
    takes its counts from the one-hot's own sum, a pass over the labels, and
    not from a column of ones between the convert and the product, which
    makes the compiler write the copy: 3.2 GB more, PR 28), it writes no row
    mask where every row is real, and it stays under the loop's peak."""
    from heat_tpu.cluster import kmeans

    n, f, k = 100_000_000, 16, 8
    compiled = kmeans._lloyd_step.lower(
        _sds((n, f), jnp.float32, one_chip), _sds((k, f), jnp.float32, one_chip), n_true=n, k=k
    ).compile()
    memory = compiled.memory_analysis()
    assert n * 4 <= memory.output_size_in_bytes < n * 4 + 2**20  # the labels
    assert memory.temp_size_in_bytes < 1_000_000_000
    assert _device_bytes(compiled) < n * f * 4 + 3_800_000_000  # the loop's arguments and temporaries
    results = [(opcode, shape) for _, opcode, shape, _ in _entry_instructions(compiled)]
    assert not [r for r in results if r[1].startswith(f"bf16[{n},")], results
    assert ("fusion", f"f32[{k}]") in results  # the counts, over the labels alone
    assert ("fusion", f"bf16[{n}]") not in results  # no row mask
    assert compiled.as_text().count("operand_precision={default,high}") == 1


@pytest.mark.parametrize("program", ["step", "loop"])
def test_lloyd_step_four_chips(comm4, for_the_chip, program):
    """Rows split over four chips: whatever a program sums over the rows
    crosses the chips together, in ONE all-reduce a program (an iteration):
    in the fit loop the ``(k, f + 1)`` product, sums and counts; in the final
    pass the sums, the counts and the inertia, combined into one tuple (what
    the compiled text shows, PR 29; PR 28 read it on the chip)."""
    from heat_tpu.cluster import kmeans

    n, f, k = 2**24, 16, 8
    fn, more = ((kmeans._lloyd_loop, {"max_iter": 30, "tol": -1.0}) if program == "loop"
                else (kmeans._lloyd_step, {}))
    compiled = fn.lower(
        _sds((n, f), jnp.float32, comm4.sharding(0)),
        _sds((k, f), jnp.float32, comm4.sharding(None)),
        n_true=n, k=k, **more,
    ).compile()
    reduces = re.findall(r"= (.*?) all-reduce(?:-start)?\(", compiled.as_text())
    assert len(reduces) == 1, reduces
    reduced = re.sub(r"\{[^}]*\}", "", reduces[0])
    assert reduced == (f"f32[{k},{f + 1}]" if program == "loop" else f"(f32[{k},{f}], f32[{k}], f32[])"), reduces
    # memory_analysis is per device: a quarter of the rows each
    assert _device_bytes(compiled) < HBM_BYTES


@pytest.mark.parametrize("kind,dtype,planes,plain,temporaries", [
    ("fft", jnp.float32, 3, 0, 3_513_555_456), ("ifft", jnp.complex64, 4, 36, 3_246_353_920)])
def test_fftn_pencil_at_the_benchmark_cell(comm4, for_the_chip, kind, dtype, planes, plain, temporaries):
    """The one program of ``ht.fft.fftn`` / ``ifftn`` on the 1024^3 cube split
    over four chips (PR 31's cell), its pencil in sixteen blocks (PR 32).  No
    all-gather: a chip never holds more than its slabs.  A complex64 array is
    two float32 planes on a TPU, so its all-to-all is two: three planes cross
    for a real cube's forward transform and four for the inverse, each block
    by block.  **The overlap, pinned where no chip is needed**: the forward
    transform's 48 exchanges are all ``all-to-all-start`` / ``-done`` pairs,
    and the schedule puts fusions between most starts and their dones (one
    exchange is in flight at a time); the inverse keeps 36 plain ones, every
    block's way in among them (PERF.md section 7).  Pinned a chip: the slab
    in, the spectrum's slab out, the temporaries (4,295,314,944 and
    5,369,153,536 B in one block) and the code's size; with the caller's
    previous result (2,147,483,648 B) still under 16 GB."""
    import importlib

    fft = importlib.import_module("heat_tpu.fft.fft")
    n = 1024
    stages = fft._planned(fft._stages(kind, ((0, None), (1, None), (2, None)), 0, None), comm4, 0, (n, n, n), n, np.dtype(dtype))
    blocks = fft._blocks(stages)
    assert blocks == 16 and stages[0][4:7] == (2, 1, 16)  # the last axis the partner, the blocks along the middle one
    compiled = fft._slab_program(comm4, 0, 3, n, stages).lower(_sds((n, n, n), dtype, comm4.sharding(0))).compile()
    txt = compiled.as_text()
    assert "all-gather" not in txt
    schedule = re.findall(r" = .*? ([a-z][a-z\-]*)\(", txt[txt.index("ENTRY"):])
    started = schedule.count("all-to-all-start")
    assert started + schedule.count("all-to-all") == planes * blocks and started == schedule.count("all-to-all-done")
    assert schedule.count("all-to-all") == plain
    between, hidden = None, []  # fusions between each start and its done
    for op in schedule:
        if op == "all-to-all-start":
            between = 0
        elif op == "all-to-all-done":
            hidden.append(between)
            between = None
        elif op == "fusion" and between is not None:
            between += 1
    assert len(hidden) == started and sum(1 for k in hidden if k) >= 3 * started // 4, hidden
    m = compiled.memory_analysis()
    slab = n ** 3 // 4 * jnp.dtype(dtype).itemsize
    assert (m.argument_size_in_bytes, m.output_size_in_bytes, m.temp_size_in_bytes) == (slab, 2_147_483_648, temporaries)
    # sixteen blocks' fusions are compiled one by one: the program's code is 111 MB on the chip for the one block's 11
    # (what ``peak_bytes_in_use`` rose by in the cell, PR 32), and stays under an eighth of a GiB
    assert 64 << 20 < m.generated_code_size_in_bytes < 128 << 20
    assert _device_bytes(compiled) + 2_147_483_648 < HBM_BYTES


def test_bucketed_data_parallel_step_four_chips(comm4, for_the_chip):
    import optax

    import heat_tpu as ht
    from __graft_entry__ import _cnn

    model = _cnn()
    batch = 256
    optimizer = optax.sgd(0.1)

    def loss_fn(logits, y):
        return optax.softmax_cross_entropy_with_integer_labels(logits, y).mean()

    dp = ht.nn.DataParallel(model, comm=comm4, optimizer=optimizer, grad_reduction="bucketed")
    dp._build(loss_fn)  # the jitted explicit step; places nothing
    replicated = NamedSharding(comm4.mesh, P())
    rows = NamedSharding(comm4.mesh, P(comm4.axis_name))
    params = jax.eval_shape(
        model.init, jax.random.PRNGKey(0), jnp.zeros((1, 28, 28, 1), jnp.float32)
    )
    opt_state = jax.eval_shape(optimizer.init, params)

    def on_mesh(tree):
        return jax.tree_util.tree_map(lambda s: _sds(s.shape, s.dtype, replicated), tree)

    compiled = dp._train_step_explicit.lower(
        on_mesh(params), on_mesh(opt_state),
        _sds((batch, 28, 28, 1), jnp.float32, rows), _sds((batch,), jnp.int32, rows),
    ).compile()
    # reduce_gradients' hand-placed psum
    assert "all-reduce" in compiled.as_text()
    assert _device_bytes(compiled) < HBM_BYTES
    n_params = sum(int(np.prod(s.shape)) for s in jax.tree_util.tree_leaves(params))
    assert n_params > 100_000  # the real CNN, not a stub


# ---------------------------------------------------------------- the scalers' cell (PR 33)
#: ``f32[33554432,50]`` as the chip lays it out: the long axis minor, 50 columns padded to 56 sublanes
TABLE_ROWS, TABLE_COLS, TABLE_BYTES = 2**25, 50, 7_516_192_768


@pytest.fixture()
def programs_of_the_eager_path(monkeypatch):
    """Run ``ht.*`` calls on SHAPES: every program the dispatch layer would
    launch is compiled for the described chip instead and kept, and its result
    is the shape it would have.  The code under test is the library's own
    (``_iop`` -> ``cast_store`` -> its refcount proof -> ``donate_argnums``);
    only the launch is taken out."""
    from heat_tpu.core import dispatch

    kept = []

    def compile_not_run(compiled, leaves, n_ops, sp, donated=False, fresh=False, key=None):
        kept.append((key[0], donated, compiled.lower(*leaves).compile()))
        return jax.eval_shape(compiled, *leaves)

    put = jax.device_put
    monkeypatch.setattr(dispatch, "_run", compile_not_run)
    monkeypatch.setattr(jax, "device_put", lambda x, s=None, **kw: (
        _sds(x.shape, x.dtype, s) if isinstance(x, jax.ShapeDtypeStruct) else put(x, s, **kw)))
    return kept


def _on_shapes(comm, shape, split):
    import heat_tpu as ht
    from heat_tpu.core import types

    return ht.DNDarray(_sds(shape, jnp.float32, comm.sharding(split)), shape, types.float32, split, ht.get_device(), comm)


def _table_stays_as_laid_out(compiled, rows=TABLE_ROWS):
    """No row-major copy of the table (``{1,0:T(8,128)}`` pads 50 lanes to
    128: 16 GiB for 6.7 GB of values) anywhere in the program."""
    assert f"f32[{rows},{TABLE_COLS}]{{1,0" not in compiled.as_text()


@pytest.mark.parametrize("name", ["StandardScaler", "MinMaxScaler", "MaxAbsScaler", "RobustScaler", "Normalizer"])
def test_scalers_write_the_table_in_place(topo, for_the_chip, programs_of_the_eager_path, name):
    """At the benchmark cell's size a ``copy=False`` transform and its inverse
    launch nothing (PR 34: their stores wait), and the read that ends them is
    ONE program that takes the table as a donated argument and aliases its
    output to it, with no temporary of the table's size: a scaler that wrote
    a second generation, 15.03 GB, would not fit beside anything."""
    import heat_tpu as ht
    from heat_tpu.parallel.comm import Communication

    comm = Communication([topo.devices[0]])
    x = _on_shapes(comm, (TABLE_ROWS, TABLE_COLS), 0)
    scaler = getattr(ht.preprocessing, name)(copy=False)
    if name == "RobustScaler":  # its fit is the selection's own program, below
        scaler.center_, scaler.iqr_ = (_on_shapes(comm, (TABLE_COLS,), None) for _ in range(2))
    else:
        scaler.fit(x)
    fits = len(programs_of_the_eager_path)
    assert scaler.transform(x) is x
    if name != "Normalizer":
        assert scaler.inverse_transform(x) is x
    reads = len(programs_of_the_eager_path)  # the Normalizer's row sums, through the chain that waits
    assert reads - fits == (1 if name == "Normalizer" else 0)
    x.larray_padded
    for kind, donated, compiled in programs_of_the_eager_path:
        m = compiled.memory_analysis()
        _table_stays_as_laid_out(compiled)
        assert m.temp_size_in_bytes <= 2**27 + 2**20, (kind, m.temp_size_in_bytes)  # the Normalizer's norms: 128 MiB
    (kind, donated, compiled), = programs_of_the_eager_path[reads:]
    m = compiled.memory_analysis()
    assert kind == "cast_store" and donated and m.alias_size_in_bytes == m.output_size_in_bytes == TABLE_BYTES
    assert TABLE_BYTES <= m.argument_size_in_bytes < TABLE_BYTES + 2**28
    # a fit is ONE program (PR 36: both moments, both extrema), which reads the table and writes rows of
    # statistics: one fusion over the whole of it; the moments' shift besides reads eight rows, and a
    # loop of at most one turn reads it again where that shift proved far off the mean
    assert fits == (0 if name in ("RobustScaler", "Normalizer") else 1)
    for kind, donated, compiled in programs_of_the_eager_path[:fits]:
        m = compiled.memory_analysis()
        assert not donated and m.argument_size_in_bytes == TABLE_BYTES and m.output_size_in_bytes <= 4096
        assert m.temp_size_in_bytes < 2**26, m.temp_size_in_bytes
        instructions = _entry_instructions(compiled)
        table, = [i[0] for i in instructions if i[1] == "parameter" and i[2] == f"f32[{TABLE_ROWS},{TABLE_COLS}]"]
        readers = [i for i in instructions if table in i[3]]
        whole = [i for i in readers if i[1] == "fusion" and f"{TABLE_COLS}]" in i[2] and "[1," not in i[2] and "[8," not in i[2]]
        assert len(whole) == 1, readers
        assert {i[1] for i in readers} <= ({"fusion", "tuple"} if name == "StandardScaler" else {"fusion"}), readers
        assert len(readers) == (3 if name == "StandardScaler" else 1), readers


def test_the_store_robust_scaler_forces_is_one_donating_program(topo, for_the_chip, programs_of_the_eager_path):
    """The benchmark's solve up to ``RobustScaler.fit``: six in-place calls
    launch nothing; ``MinMaxScaler.fit`` and ``MaxAbsScaler.fit`` read the
    table THROUGH the waiting chain (four and eight operations deep), one
    program each (PR 36), and write rows of 512 B and no table; the read that the selection forces
    runs the ten operations as one store: the table a donated argument of
    7,516,192,768 B, the output aliased to it, 0 B of temporaries, one
    fusion, and every division still there (the simplifier would make
    ``(x / a) / b`` ``x / (a * b)`` across MinMax's inverse and MaxAbs's
    transform: ``dispatch._stored`` stands between)."""
    import heat_tpu as ht
    from heat_tpu.parallel.comm import Communication

    comm = Communication([topo.devices[0]])
    x = _on_shapes(comm, (TABLE_ROWS, TABLE_COLS), 0)
    through = {}
    for name in ("StandardScaler", "MinMaxScaler", "MaxAbsScaler"):
        scaler = getattr(ht.preprocessing, name)(copy=False)
        before = len(programs_of_the_eager_path)
        scaler.fit(x)
        through[name] = programs_of_the_eager_path[before:]
        before = len(programs_of_the_eager_path)
        assert scaler.inverse_transform(scaler.transform(x)) is x and len(programs_of_the_eager_path) == before
    assert [len(v) for v in through.values()] == [1, 1, 1]
    for name, fits in through.items():
        for kind, donated, compiled in fits:
            m = compiled.memory_analysis()
            _table_stays_as_laid_out(compiled)
            assert not donated and m.output_size_in_bytes <= 3 * 512 and m.temp_size_in_bytes < 2**20, (name, m.temp_size_in_bytes)
            assert TABLE_BYTES <= m.argument_size_in_bytes < TABLE_BYTES + 2**16
    # both moments THROUGH the ten waiting operations: the shift's rows are taken of the leaves, so the
    # chain's value has one reader and is never written (as a slice's operand it was kept: 7.5 GB)
    before = len(programs_of_the_eager_path)
    ht.preprocessing.StandardScaler().fit(x)
    (kind, donated, compiled), = programs_of_the_eager_path[before:]
    m = compiled.memory_analysis()
    _table_stays_as_laid_out(compiled)
    assert kind == "chain" and not donated and m.temp_size_in_bytes < 2**20, m.temp_size_in_bytes
    before = len(programs_of_the_eager_path)
    x.larray_padded  # what `statistics.percentile` does first
    (kind, donated, compiled), = programs_of_the_eager_path[before:]
    m = compiled.memory_analysis()
    _table_stays_as_laid_out(compiled)
    assert kind == "cast_store" and donated and m.alias_size_in_bytes == m.output_size_in_bytes == TABLE_BYTES
    assert TABLE_BYTES <= m.argument_size_in_bytes < TABLE_BYTES + 2**16 and m.temp_size_in_bytes == 0
    text = compiled.as_text()
    table = f"f32[{TABLE_ROWS},{TABLE_COLS}]"
    assert len(re.findall(rf"= {re.escape(table)}\S* fusion\(", text)) == 1
    assert len(re.findall(rf"= {re.escape(table)}\S* divide\(", text)) == 3  # by sqrt(var_), scale_ and scale_


def _selection(rows, q=(25.0, 50.0, 75.0)):
    pos = np.asarray(q) / 100.0 * (rows - 1)
    lows = tuple(int(v) for v in np.floor(pos))
    return dict(axis=0, lows=lows, with_high=True, plan=tuple((i, True, float(p - np.floor(p))) for i, p in enumerate(pos)),
                method="linear", keepdims=False, scalar_q=False, n_true=rows)


def test_robust_scaler_fit_selects_without_a_sorted_copy(one_chip, for_the_chip):
    """``RobustScaler.fit``'s one program at 2^25 x 50: no ``sort``, the
    table in its argument layout and read once a pass (16 counting passes of
    2 bits, and the upper neighbours' count and minimum as one ``reduce``: 17
    reads, PR 36; 18 while they were two reductions), under 64 MiB beside it.  ``jnp.percentile`` of the same table is refused by the
    compiler for 21 GB."""
    from heat_tpu.core import statistics

    compiled = statistics._select_program.lower(
        _sds((TABLE_ROWS, TABLE_COLS), jnp.float32, one_chip), **_selection(TABLE_ROWS)).compile()
    m = compiled.memory_analysis()
    text = compiled.as_text()
    assert " sort(" not in text and "all-gather" not in text
    _table_stays_as_laid_out(compiled)
    assert m.argument_size_in_bytes == TABLE_BYTES and m.temp_size_in_bytes < 2**26 and m.output_size_in_bytes <= 4096
    reads = [i for i in _entry_instructions(compiled) if "x.1" in i[3]]
    assert len(reads) == statistics._select_passes(jnp.float32, 3, True) == 17, len(reads)
    with pytest.raises(Exception, match="(?i)hbm|memory|RESOURCE_EXHAUSTED"):
        jax.jit(lambda a: jnp.percentile(a, jnp.asarray([25.0, 50.0, 75.0]), axis=0)).lower(
            _sds((TABLE_ROWS, TABLE_COLS), jnp.float32, one_chip)).compile()


@pytest.mark.parametrize("pad", [0, 3], ids=["every_row_real", "three_pad_rows"])
def test_selection_over_four_chips_exchanges_counts(comm4, for_the_chip, pad):
    """Rows split over four chips: a counting pass holds ONE all-reduce, of a
    (ranks x pivots x columns) array of counts, and the last pass one of
    counts and one of minima; nothing is gathered or sorted across chips."""
    from heat_tpu.core import statistics

    rows = 2**24
    args = dict(_selection(rows - pad))
    program = statistics._select_program_split(comm4, *args.values(), pad > 0)
    compiled = program.lower(_sds((rows, TABLE_COLS), jnp.float32, comm4.sharding(0))).compile()
    text = compiled.as_text()
    assert "all-gather" not in text and " sort(" not in text and "all-to-all" not in text
    _table_stays_as_laid_out(compiled, rows // 4)
    reduced = [re.sub(r"\{[^}]*\}", "", r) for r in re.findall(r"= (.*?) all-reduce(?:-start)?\(", text)]
    passes = statistics._select_passes(jnp.float32, 3, True)
    assert len(reduced) == passes + 1, reduced
    # three ranks x three pivots; the first pass counts the NaNs too
    assert reduced[0] == f"s32[10,1,{TABLE_COLS}]" and set(reduced[1:passes - 1]) == {f"s32[3,3,1,{TABLE_COLS}]"}, reduced[:3]
    assert sorted(reduced[-2:]) == [f"s32[3,1,{TABLE_COLS}]", f"u32[3,1,{TABLE_COLS}]"], reduced[-2:]
    assert compiled.memory_analysis().temp_size_in_bytes < 2**26


# ------------------------------------------------------------ the KMedians cell (PR 37)
KMED_ROWS, KMED_COLS, KMED_K = 2**28, 3, 4
KMED_TABLE_BYTES = KMED_ROWS * 16  # the long axis minor, 3 columns padded to 4 sublanes


def _while_bodies(text: str) -> dict:
    """{body's name: (its instructions, its condition's text)} of a program's loops."""
    found = {}
    for cond, body in set(re.findall(r" while\([^)]*\), condition=%([^ ,]+), body=%([^ ,]+)", text)):
        cond_text = re.search(r"^%" + re.escape(cond) + r" [^\n]*\{\n(.*?)^\}", text, re.S | re.M).group(1)
        found[body] = (_computation_instructions(text, body), cond_text)
    return found


#: opcodes that move nothing: a value passed on is not a read of it
PASSED_ON = ("parameter", "tuple", "get-tuple-element", "bitcast", "opt-barrier")


def test_kmedians_loop_at_the_benchmark_cell(one_chip, for_the_chip):
    """The KMedians cell, 2^28 x 3 points and 4 clusters.  The fit loop makes
    ONE copy of the table before its first turn, column by column with every
    register full (3.22 GB for the table's 4.29 as laid out, as KMeans makes
    its bfloat16 copy), and every turn reads that copy 18 times: the
    assignment (ONE fusion, the only one that reads the copy), 16 counting
    passes of 2 bits that all four clusters share (the Pallas kernel
    ``kmedians_count``, one call in a loop of 16 turns) and the neighbours'
    pass (the kernel's second body, ``kmedians_neighbours``, one call behind
    that loop: PR 38; a ``reduce`` of twelve operands over the copy and four
    masks of the labels before), = ``passes_an_iteration`` = the root span's
    ``passes`` = the benchmark's ``kmedians_passes``.  Beside table and copy
    it holds the labels, the one thing of the rows' length a turn writes, and
    under 64 MiB more: no ``sort``, no array of rows x clusters, no mask of
    the labels, nothing else of the copy's shape (the order key, a masked
    copy: each was one while the points were a constant of the loop).  The
    clusters' sizes are the first counting pass's totals: no fusion reads the
    labels but the kernels.  The parent's loop (``jnp.nanmedian`` of a masked
    copy, once a cluster) is refused for 137 GB."""
    from heat_tpu.cluster import kmedians
    from heat_tpu.core import kernels

    n, f, k = KMED_ROWS, KMED_COLS, KMED_K
    packed = f"[{f},{n // kernels.COUNT_LANES},{kernels.COUNT_LANES}]"
    column = f"[{n // kernels.COUNT_LANES},{kernels.COUNT_LANES}]"
    compiled = kmedians._programs(None, n, 5, -1.0)[0].lower(
        _sds((n, f), jnp.float32, one_chip), _sds((k, f), jnp.float32, one_chip)).compile()
    m = compiled.memory_analysis()
    text = compiled.as_text()
    assert " sort(" not in text
    assert KMED_TABLE_BYTES <= m.argument_size_in_bytes < KMED_TABLE_BYTES + 2**16
    assert m.temp_size_in_bytes < n * f * 4 + n * 4 + 2**26 < 4.4e9 and m.output_size_in_bytes <= 4096
    assert _device_bytes(compiled) < 0.6 * HBM_BYTES
    copies = [(opcode, shape) for _, opcode, shape, _ in _entry_instructions(compiled) if packed in shape and opcode not in PASSED_ON]
    assert copies == [("fusion", "f32" + packed), ("while", copies[-1][1])], copies  # the copy, made once, and the loop that holds it
    loops = _while_bodies(text)
    (turn,) = [v for v in loops.values() if any(opcode == "while" for _, opcode, _, _ in v[0])]  # the fit loop holds the passes'
    (passes,) = [v for v in loops.values() if v is not turn]

    def reads_of_the_copy(body):
        (copy,) = {name for name, opcode, shape, _ in body if opcode == "get-tuple-element" and shape == "f32" + packed}
        views = {copy} | {name for name, opcode, _, operands in body if opcode == "bitcast" and copy in operands}
        return [(opcode, shape) for _, opcode, shape, operands in body if views & set(operands) and opcode not in PASSED_ON]

    # a turn of the fit loop: the assignment (ONE fusion), the counting passes (a loop of their own), the neighbours' kernel
    reads = reads_of_the_copy(turn[0])  # (the passes' loop takes the copy inside a tuple)
    minima = f"[{k},{f},8,{kernels.COUNT_LANES}]"
    assert reads == [("fusion", "s32" + column), ("custom-call", "s32" + minima)], reads
    # a counting pass: the kernel, ONE call site, 16 turns
    assert reads_of_the_copy(passes[0]) == [("custom-call", f"s32[{k},4,{f},8,{kernels.COUNT_LANES}]")]
    # two kernels in the text, each name once
    assert text.count('custom_call_target="tpu_custom_call"') == 2
    assert len(re.findall(r"%kmedians_count[.\d]* = ", text)) == len(re.findall(r"%kmedians_neighbours[.\d]* = ", text)) == 1
    # the labels are read by the kernels alone: their sizes are the first counting pass's totals
    (labels,) = [name for name, opcode, shape, _ in turn[0] if opcode == "fusion" and shape == "s32" + column]
    assert [opcode for _, opcode, _, operands in turn[0] if labels in operands and opcode not in PASSED_ON] == ["custom-call"]
    assert re.search(r"constant\(16\)", passes[1]) and kmedians.passes_an_iteration(jnp.float32, k) == 1 + 16 + 1 == 18
    for body, _ in (turn, passes):
        made = [(opcode, shape) for _, opcode, shape, _ in body if packed in shape and opcode not in PASSED_ON + ("while",)]
        assert made == [], made  # nothing of the copy's shape is written
    # what a turn writes of the rows' length (inside a fusion nothing is written): no rows x clusters among it
    tall = [(opcode, shape) for _, opcode, shape, _ in turn[0] if column in shape and opcode not in PASSED_ON + ("while",)]
    assert tall == [("fusion", "s32" + column)], tall  # the labels alone: no mask of them


def test_kmedians_final_pass_at_the_benchmark_cell(one_chip, for_the_chip):
    """The fit's last pass: the copy, the labels (one fusion, one read of the
    copy) and the inertia (one more read); no distances to every center in
    between, and the labels laid out as the caller's (one pass over them)."""
    from heat_tpu.cluster import kmedians
    from heat_tpu.core import kernels

    n, f, k = KMED_ROWS, KMED_COLS, KMED_K
    packed = f"f32[{f},{n // kernels.COUNT_LANES},{kernels.COUNT_LANES}]"
    compiled = kmedians._programs(None, n, 5, -1.0)[1].lower(
        _sds((n, f), jnp.float32, one_chip), _sds((k, f), jnp.float32, one_chip)).compile()
    m = compiled.memory_analysis()
    assert n * 4 <= m.output_size_in_bytes < n * 4 + 2**16 and m.temp_size_in_bytes < n * f * 4 + n * 4 + 2**26
    instructions = _entry_instructions(compiled)
    (copy,) = [name for name, opcode, shape, _ in instructions if shape == packed and opcode == "fusion"]
    reads = [(opcode, shape) for _, opcode, shape, operands in instructions if copy in operands and opcode not in PASSED_ON]
    assert sorted(reads) == [("fusion", "f32[]"), ("fusion", f"s32[{n // kernels.COUNT_LANES},{kernels.COUNT_LANES}]")], reads


@pytest.mark.parametrize("pad", [0, 3], ids=["every_row_real", "three_pad_rows"])
def test_kmedians_over_four_chips_exchanges_counts(comm4, for_the_chip, pad):
    """Rows split over four chips: each chip assigns and counts in its own
    rows; what crosses the chips are integers of (clusters x digits x
    features) a pass, and the neighbours' minima, which carry the evidence
    of a NaN; the clusters' sizes cross as the first pass's counts, which
    they are, and no longer by themselves; nothing is gathered, exchanged or
    sorted."""
    from heat_tpu.cluster import kmedians

    n, f, k = KMED_ROWS, KMED_COLS, KMED_K
    loop, final = kmedians._programs(comm4, n - pad, 5, -1.0)
    args = (_sds((n, f), jnp.float32, comm4.sharding(0)), _sds((k, f), jnp.float32, comm4.sharding(None)))
    compiled = loop.lower(*args).compile()
    text = compiled.as_text()
    assert "all-gather" not in text and " sort(" not in text and "all-to-all" not in text
    reduced = [re.sub(r"\{[^}]*\}", "", r) for r in re.findall(r"= (.*?) all-reduce(?:-start)?\(", text)]
    # a pass's counts (a digit's a group a column; once in the text: the passes are a loop) and the neighbours' minima
    assert sorted(reduced) == sorted([f"s32[{k},4,{f}]", f"u32[{k},{f}]"]), reduced
    m = compiled.memory_analysis()
    assert m.temp_size_in_bytes < (n // 4) * (f * 4 + 4) + 2**26  # a chip's copy and labels
    text = final.lower(*args).compile().as_text()
    assert "all-gather" not in text and " sort(" not in text
    assert [re.sub(r"\{[^}]*\}", "", r) for r in re.findall(r"= (.*?) all-reduce(?:-start)?\(", text)] == ["f32[]"]


LASSO_ROWS, LASSO_COLS = 10_000_000, 128


def _lasso_program(sharding_of, comm=None, rows=LASSO_ROWS, syrk_ok=True, y_column=False):
    from heat_tpu.regression import lasso

    plan = dict(n=rows, gram=True, syrk_ok=syrk_ok, comm=comm, max_iter=100, phase="fit")
    return lasso._program.lower(
        _sds((rows, LASSO_COLS), jnp.float32, sharding_of(0)),
        _sds((rows, 1) if y_column else (rows,), jnp.float32, sharding_of(0)), (),
        _sds((), jnp.float32, sharding_of(None)), _sds((), jnp.float32, sharding_of(None)),
        _sds((LASSO_COLS + 1,), jnp.float32, sharding_of(None)), **plan).compile()


def test_lasso_fit_at_the_benchmark_cell(one_chip, for_the_chip):
    """The Lasso cell, 10^7 x 128 and 100 sweeps, ``y`` the ``(rows, 1)``
    column the cell hands in: ONE program, ONE read of the table (PR 40; two
    until then).  No second table: nothing of the table's size is an operand
    but the table or a temporary at all (the parent of PR 39 concatenated a
    column of ones to it: ``f32[10^7, 129]``), and ``y`` goes to the kernel as
    it lies, a ``(1, rows)`` view of the column's own bytes (no copy: until
    PR 40 the program made ``y`` a vector, 40 MB, 0.25 ms a fit, and the
    column as ``(rows, 1)`` in rows of lanes would be a second table).  The
    table's readers are the Gram kernel with the moments' body
    (``gram_syrk_moments``, its custom call once in the text, its operands
    the table, the row of shifts it takes from every tile, ``y`` and ``y``'s
    shift) and the small fusions of the first 4,096 rows (the shift) and the
    rows past the last tile (their Gram and their moments); no loop carries
    it.  The descent is ONE kernel (``lasso_cd``, its custom call once in the
    text): no loop of XLA operations holds its 12,900 turns (two fusions a
    turn at the least, 25,800 events a fit: a traced window of 4 s overran
    the profiler), so a fit is a dozen operations on the device and the
    kernels are among the trace's ten largest, where the benchmark's readers
    find them by the names that hold ``gram_syrk`` and ``lasso_cd``."""
    compiled = _lasso_program(lambda split: one_chip, y_column=True)
    n, f = LASSO_ROWS, LASSO_COLS
    m = compiled.memory_analysis()
    text = compiled.as_text()
    assert n * f * 4 + n * 4 <= m.argument_size_in_bytes < n * f * 4 + n * 4 + 2**16
    assert m.temp_size_in_bytes < 2**26 and m.output_size_in_bytes <= 4096
    assert text.count('custom_call_target="tpu_custom_call"') == 2
    assert len(re.findall(r"%gram_syrk_moments[.\d]* = ", text)) == len(re.findall(r"%lasso_cd[.\d]* = ", text)) == 1
    assert " while(" not in text  # neither the moments nor the sweeps are a loop of XLA operations
    instructions = _entry_instructions(compiled)
    (table,) = [name for name, opcode, shape, _ in instructions if opcode == "parameter" and shape == f"f32[{n},{f}]"]
    (column,) = [name for name, opcode, shape, _ in instructions if opcode == "parameter" and shape == f"f32[{n},1]"]
    readers = sorted((opcode, name) for name, opcode, _, operands in instructions if table in operands and opcode not in PASSED_ON)
    assert [opcode for opcode, _ in readers] == ["custom-call"] + ["fusion"] * 3, readers
    (kernel,) = [i for i in instructions if i[1] == "custom-call" and i[0].startswith("gram_syrk")]
    shape_of = {name: shape for name, _, shape, _ in instructions}
    opcode_of = {name: opcode for name, opcode, _, _ in instructions}
    operands_of = {name: operands for name, _, _, operands in instructions}
    assert kernel[3][0] == table and (kernel[1], kernel[0]) in readers
    assert [shape_of[o] for o in kernel[3][1:]] == [f"f32[1,{f}]", f"f32[1,{n}]", "f32[1,1]"]
    assert opcode_of[kernel[3][2]] == "bitcast" and operands_of[kernel[3][2]] == [column]  # y as it lies: no copy
    # the fusions read the first rows (4,096: the shift) and the rows past the last tile (1,664 rows, twice: their
    # Gram and their moments): what they take of the table is that small
    for _, name in readers[1:]:
        called = _computation_instructions(text, re.search(r"%" + re.escape(name) + r" = [^\n]* calls=%([^ ,\n]+)", text).group(1))
        (inside,) = [i[0] for i in called if i[1] == "parameter" and i[2] == f"f32[{n},{f}]"]
        taken = [int(re.match(r"f32\[(\d+),128\]", shape).group(1)) for _, _, shape, operands in called if inside in operands]
        assert taken and max(taken) <= 4096, (name, taken)
    # nothing of the column's size but the parameter and views of its bytes (the kernel's, the tail fusion's)
    assert {(opcode_of[name], tuple(operands_of[name])) for name, _, shape, _ in instructions
            if str(n) in shape and f"{n},{f}" not in shape and name != column} == {("bitcast", (column,))}
    (descent,) = [i for i in instructions if i[1] == "custom-call" and i[0].startswith("lasso_cd")]
    assert not any(f"[{n}" in shape for name, _, shape, _ in instructions if name in descent[3])
    assert len([i for i in instructions if i[1] not in PASSED_ON + ("constant",)]) < 60
    assert _device_bytes(compiled) < 0.4 * HBM_BYTES


@pytest.mark.parametrize("coordinates", [129, 130, 1024], ids=["cell", "spills_a_lane", "the_bound"])
def test_lasso_descent_kernel_up_to_its_bound(one_chip, for_the_chip, coordinates):
    """``cd_sweeps`` at the cell's 129 coordinates, one lane on, and at
    ``_CD_MAX`` itself, the most `cd_supported` admits: ``A`` and the turns'
    last values, (1024, 1024) float32 each, are 8 MiB, and the kernel with
    them fits the chip's fast memory under the default limit.  One more
    coordinate, or another type, and `lasso._gram_form` takes the residual
    form."""
    from heat_tpu.core import kernels

    assert kernels.cd_supported(coordinates, jnp.float32) and kernels._CD_MAX == 1024
    assert not kernels.cd_supported(kernels._CD_MAX + 1, jnp.float32) and not kernels.cd_supported(129, jnp.float64)
    small = lambda *shape: _sds(shape, jnp.float32, one_chip)
    compiled = jax.jit(kernels.cd_sweeps, static_argnums=7).lower(
        small(coordinates, coordinates), small(coordinates), small(coordinates), small(coordinates), small(coordinates), small(),
        small(coordinates), 100).compile()
    text = compiled.as_text()
    assert len(re.findall(r"%lasso_cd[.\d]* = ", text)) == 1 and " while(" not in text
    assert _device_bytes(compiled) < 2**26


def test_lasso_over_four_chips_sums_the_parts(comm4, for_the_chip):
    """Rows split over four chips: each chip's Gram (an XLA product: the
    gate keeps the kernel for one device, R8) and moments, all-reduced;
    nothing is gathered, nothing of a chip's rows crosses, and the descent's
    kernel runs on every chip alike.  (Rows no four chips divide come padded and
    zeroed by the caller: the same program.)"""
    compiled = _lasso_program(comm4.sharding, comm=comm4, syrk_ok=False)
    text = compiled.as_text()
    assert "all-gather" not in text and "all-to-all" not in text and "gram_syrk" not in text
    reduced = [re.sub(r"\{[^}]*\}", "", r) for r in re.findall(r"= (.*?) all-reduce(?:-start)?\(", text)]
    assert reduced and all(str(LASSO_ROWS // 4) not in r for r in reduced), reduced
    assert compiled.memory_analysis().temp_size_in_bytes < 2**26


# ---------------------------------------------------------------- the PCA cell
PCA_ROWS, PCA_COLS, PCA_K, PCA_POWER = 1_281_167, 2048, 256, 4
#: the sketch, ``f32[1281167,266]``, as the chip lays it out: 266 on the sublanes (272), the rows on the lanes
PCA_SKETCH_BYTES = 272 * 1_281_280 * 4


def test_pca_randomized_fit_at_the_benchmark_cell(topo, for_the_chip, programs_of_the_eager_path, monkeypatch):
    """The PCA cell: ``PCA(n_components=256, svd_solver="randomized",
    iterated_power=4).fit`` of a resident 1,281,167 x 2048 float32 table,
    every program compiled for the described chip.  The moments are ONE
    program of the dispatch layer (``statistics.mean_var``) that reads the
    table in one fusion; the solver is ONE program (`pca._randomized_fit`)
    whose loop of ``iterated_power + 1`` turns reads it twice a turn, in the
    two products (``X Q``, ``Q^T X``, each one fusion with the centring
    inside) and nowhere else, and that holds no buffer of the table's size
    but the table (``X - mean`` made as an array would be a second 10.5 GB
    table; no ``U`` is made): beside it two sketches.  The reads add up to the root span's
    ``passes``, which the benchmark's ``pca_table_reads`` reads, and the
    products' results have the shapes ``pca_products_ms`` finds them by."""
    import functools

    import heat_tpu as ht
    from heat_tpu import telemetry
    from heat_tpu.decomposition import pca
    from heat_tpu.parallel.comm import Communication

    comm = Communication([topo.devices[0]])
    one_chip = SingleDeviceSharding(topo.devices[0])
    solvers = []

    def compile_not_run(*args, **plan):
        shapes = [_sds(a.shape, a.dtype, one_chip) for a in args]
        solvers.append((plan, pca._randomized_program.lower(*shapes, **plan).compile()))
        return jax.eval_shape(functools.partial(pca._randomized_program, **plan), *shapes)

    monkeypatch.setattr(pca, "_randomized_fit", compile_not_run)
    n, f, ell = PCA_ROWS, PCA_COLS, PCA_K + 10
    x = _on_shapes(comm, (n, f), 0)
    was = telemetry.set_tracing(True)
    telemetry.clear_spans()
    try:
        ht.decomposition.PCA(n_components=PCA_K, svd_solver="randomized", iterated_power=PCA_POWER,
                             n_oversamples=10, random_state=7).fit(x)
        (root,) = [r for r in telemetry.get_spans() if r.name == "ht.decomposition.PCA.fit"]
    finally:
        telemetry.set_tracing(was)
        telemetry.clear_spans()
    table = f"f32[{n},{f}]"
    # the moments: one program, one fusion over the whole table (and eight rows of it for the shift; a loop of
    # at most one turn would read it again where that shift proved far off the mean)
    ((kind, donated, moments),) = programs_of_the_eager_path
    m = moments.memory_analysis()
    assert not donated and n * f * 4 <= m.argument_size_in_bytes < n * f * 4 + 2**20 and m.temp_size_in_bytes < 2**26
    instructions = _entry_instructions(moments)
    (param,) = [i[0] for i in instructions if i[1] == "parameter" and i[2] == table]
    whole = [i for i in instructions if param in i[3] and i[1] == "fusion" and "[1," not in i[2] and "[8," not in i[2]]
    assert [i[2] for i in whole] == [f"(f32[{f}], f32[{f}])"], whole
    # the solver: no table-sized buffer, the two sketches beside the table
    ((plan, compiled),) = solvers
    assert plan == {"n": n, "k": PCA_K, "ell": ell, "power_iter": PCA_POWER}
    m = compiled.memory_analysis()
    assert n * f * 4 <= m.argument_size_in_bytes < n * f * 4 + 2**16 and m.output_size_in_bytes < 2**22
    assert 2 * PCA_SKETCH_BYTES <= m.temp_size_in_bytes < 2 * PCA_SKETCH_BYTES + 2**26, m.temp_size_in_bytes
    assert _device_bytes(compiled) < HBM_BYTES
    text = compiled.as_text()
    fused = set(re.findall(r"kind=k\w+, calls=%([^ ,]+)", text))
    entry = _entry_instructions(compiled)
    computations = {c: _computation_instructions(text, c) for c in set(re.findall(r"^%([^ ]+) \(", text, re.M)) - fused}
    for name, instructions in [("ENTRY", entry), *computations.items()]:
        made = [i for i in instructions if i[2] == table and i[1] not in PASSED_ON + ("while",)]
        assert not made, (name, made)
    # the reads: none outside the loop, two a turn inside it
    (param,) = [i[0] for i in entry if i[1] == "parameter" and i[2] == table]
    assert {i[1] for i in entry if param in i[3]} <= {"tuple", "while"}
    reads = {}
    for body, cond in _while_bodies(text).values():
        if any(i[1] == "parameter" and table in i[2] for i in body):
            (got,) = [i[0] for i in body if i[1] == "get-tuple-element" and i[2] == table]
            (turns,) = re.findall(r"constant\((\d+)\)", cond)
            reads[int(turns)] = sorted((i[1], i[2]) for i in body if got in i[3] and i[1] not in PASSED_ON)
    assert reads == {PCA_POWER + 1: [("fusion", f"f32[{ell},{n}]"), ("fusion", f"f32[{ell},{f}]")]}, reads
    # the span's count is the compiled programs': the moments' fusion and the loop's two readers a turn
    assert root.attrs["passes"] == len(whole) + len(reads[PCA_POWER + 1]) * (PCA_POWER + 1) == 11
