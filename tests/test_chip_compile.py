"""What only the TPU's compiler can say, asked without a chip.

The engine's TPU-only branches (one-hot-matmul segmented reductions,
the f32 detour around 64-bit bitcasts, narrowed uploads widened
in-trace) are reached on the CPU backend only through test switches,
so a CPU-green suite says nothing about whether today's TPU compiler
still accepts them at the bench's real width. These tests compile the
main path's kernels for a DESCRIBED v5e chip (`v5e:2x2`, no chip
attached) at 4.5M-row partitions — 36M rows in 8 files — with x64 on.
Each takes seconds; whole fused stages (two of which take ~2 minutes
of compiler time each, CHANGES.md PR 21) are rehearsed by hand, not
here. A compile that passes is not a chip run and is never reported as
one.

Everything that touches the topology lives in the module-scoped
fixtures below: only the worker that runs this file loads the TPU's
library, and only once a test of this file has started.
"""

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import Mesh, NamedSharding, SingleDeviceSharding
from jax.sharding import PartitionSpec as P

from spark_rapids_tpu.columnar.batch import ColumnBatch, DeviceColumn
from spark_rapids_tpu.sqltypes.datatypes import (
    StructField,
    StructType,
    double,
    long,
)

ROWS = 4_500_000          # one of the bench's 8 fact files
BUILD_CAP = 1 << 16       # the 2000-row dim and the 4000-row dup-key
#                           dim both upload at the 64Ki floor
A2A_SHARD = 1 << 18       # rows per device in the all-to-all case


@pytest.fixture(scope="module")
def topo():
    import importlib.util

    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        # with the TPU's library installed these tests are held to
        # PASS: some of them guard a measured time against what the
        # compiler decides (test_late_lookup_join_at_q12_width)
        if importlib.util.find_spec("libtpu") is not None:
            raise
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_persistent_cache():
    """A compile for a described device is written to jax's persistent
    cache but cannot be read back without a chip: keep it out."""
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture
def as_tpu(monkeypatch, no_persistent_cache):
    """Code that asks `jax.default_backend()` while it is traced takes
    its TPU branch: a described device does not change the default."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _col(dtype, np_dtype, n, sharding, vrange=None):
    return DeviceColumn(dtype, _sds((n,), np_dtype, sharding),
                        _sds((n,), jnp.bool_, sharding), vrange=vrange)


def _batch(cols, names, sharding):
    schema = StructType([StructField(nm, c.dtype, True)
                         for nm, c in zip(names, cols)])
    return ColumnBatch(schema, list(cols),
                       _sds((), jnp.int32, sharding))


def _compile(fn, *avals):
    return jax.jit(fn).lower(*avals).compile()


# ------------------------------------------------ the refusal itself

def test_f64_bitcast_still_refused(one_chip, no_persistent_cache):
    """`ops.common.supports_64bit_bitcast()` answers False on a TPU
    because the x64 rewrite refuses this HLO. The day this test fails,
    the compiler accepts it: drop the f32 detours in ops/common.py and
    ops/hashing.py (and this test)."""
    x = _sds((ROWS,), jnp.float64, one_chip)
    with pytest.raises(Exception, match="X64 element types"):
        _compile(lambda a: jax.lax.bitcast_convert_type(a, jnp.int64), x)


# ----------------------------------------------- segmented reductions

@pytest.mark.parametrize("bins", [14, 2050],
                         ids=["12-groups", "2000-groups"])
def test_matmul_segmented_sum_count(one_chip, as_tpu, bins):
    """The binned group-by's hot kernel: f64 sum + count as one-hot
    matmuls (ops/segmented.py), at the q5 region key (12 values + null
    and dead bins) and at the store key (quantized to [0, 2047])."""
    from spark_rapids_tpu.columnar.batch import next_capacity
    from spark_rapids_tpu.ops import segmented

    cap = next_capacity(bins)

    def kernel(values, valid, gid):
        with segmented.unsorted_gids(), segmented.binned_bins(bins):
            assert segmented.mm_bins_active() == bins
            return segmented.seg_sum_count(values, valid, gid, cap)

    sweeps = segmented.mm_traced_sweeps
    c = _compile(kernel, _sds((ROWS,), jnp.float64, one_chip),
                 _sds((ROWS,), jnp.bool_, one_chip),
                 _sds((ROWS,), jnp.int32, one_chip))
    assert segmented.mm_traced_sweeps > sweeps, "matmul path not taken"
    assert c.memory_analysis().temp_size_in_bytes < (2 << 30)


@pytest.mark.parametrize("vbound", [(0, 127), None],
                         ids=["bounded", "limbs"])
def test_matmul_int_sum(one_chip, as_tpu, vbound):
    """Exact int64 sums ride the MXU in f32 chunks with an i64 carry:
    one weight vector for a vrange-bounded column (qty in [0, 127]),
    eight 8-bit limbs for an int64 column with no bound, and no
    scatter either way."""
    from spark_rapids_tpu.ops import segmented

    def kernel(values, valid, gid):
        with segmented.unsorted_gids(), segmented.binned_bins(2050):
            return segmented.seg_sum(values, valid, gid, 4096,
                                     vbound=vbound)

    sweeps = segmented.mm_traced_sweeps
    c = _compile(kernel, _sds((ROWS,), jnp.int64, one_chip),
                 _sds((ROWS,), jnp.bool_, one_chip),
                 _sds((ROWS,), jnp.int32, one_chip))
    assert segmented.mm_traced_sweeps > sweeps, "matmul path not taken"
    assert " scatter(" not in c.as_text()
    assert c.memory_analysis().temp_size_in_bytes < (2 << 30)


@pytest.mark.parametrize("np_dtype", [jnp.float64, jnp.int64],
                         ids=["f64", "i64"])
def test_keyless_dense_reductions(one_chip, as_tpu, np_dtype):
    """A keyless aggregate's reductions (Q6's sum and its null-tracking
    count, a min and a max) as whole-array reduces in the buffer's own
    type: the TPU compiler takes them at a partition's width and the
    compiled program holds no scatter."""
    from spark_rapids_tpu.ops import segmented

    def kernel(values, valid, gid):
        with segmented.one_segment():
            return (segmented.seg_sum_count(values, valid, gid, ROWS),
                    segmented.seg_min(values, valid, gid, ROWS),
                    segmented.seg_max(values, valid, gid, ROWS))

    dense = segmented.dense_traced_reductions
    c = _compile(kernel, _sds((ROWS,), np_dtype, one_chip),
                 _sds((ROWS,), jnp.bool_, one_chip),
                 _sds((ROWS,), jnp.int32, one_chip))
    assert segmented.dense_traced_reductions == dense + 4
    assert "scatter" not in c.as_text()
    assert c.memory_analysis().temp_size_in_bytes < (64 << 20)


# ------------------------------------------------------- lookup join

def _sorted_build(one_chip):
    """A BuildTable as `joinops.build_side` sorts it (two minutes of
    compiler time, not spent here): probing it is what these tests
    compile."""
    from spark_rapids_tpu.ops import joinops

    batch = _batch([_col(long, jnp.int64, BUILD_CAP, one_chip),
                    _col(double, jnp.float64, BUILD_CAP, one_chip)],
                   ["store", "discount"], one_chip)
    return joinops.BuildTable(
        batch, [_sds((BUILD_CAP,), jnp.int64, one_chip)],
        _sds((), jnp.int32, one_chip))


def _probe(one_chip):
    return _batch([_col(long, jnp.int64, ROWS, one_chip),
                   _col(double, jnp.float64, ROWS, one_chip)],
                  ["store", "amount"], one_chip)


def test_lookup_join_probe_and_gather(one_chip, as_tpu):
    """Binary search of a sorted build + single-match gather over
    int64 keys (ops/joinops.py; exec/fused.py lookup_join): a 4.5M-row
    probe partition against the dim, uniqueness flag included."""
    from spark_rapids_tpu.ops import joinops

    def kernel(bt, probe):
        lo, counts = joinops.probe_ranges(bt, probe, [0])
        safe = jnp.clip(lo, 0, bt.batch.capacity - 1)
        gathered = bt.batch.columns[1].gather(safe)
        return (gathered.data, gathered.validity & (counts > 0),
                jnp.any(counts > 1))

    _compile(kernel, _sorted_build(one_chip), _probe(one_chip))


Q12_PART = 7_864_320      # slots of one of SF10 lineitem's 8 parts
Q12_BUILD = 15_728_640    # slots of SF10 orders, its 8 parts end to end


def test_survivor_lookup_join_at_q12_width(one_chip, as_tpu):
    """The lookup join under a filter, at tpch_q12_join_resident's own
    width (exec/fused.py `survivors` + `lookup_join`): the mask's
    survivors brought to 1/64 of a part's slots by `front_row_ids`,
    then ONE search of the 15.7M-slot build table, whose keys the
    build side narrowed to 32 bits, by the 64-bit probe keys. Small:
    nothing here is as wide as the part."""
    from spark_rapids_tpu.exec.fused import survivor_capacity
    from spark_rapids_tpu.ops import joinops

    cap = survivor_capacity(Q12_PART)
    assert cap == 122_880
    build = joinops.BuildTable(
        _batch([_col(long, jnp.int64, Q12_BUILD, one_chip),
                _col(long, jnp.int64, Q12_BUILD, one_chip)],
               ["o_orderkey", "o_code"], one_chip),
        [_sds((Q12_BUILD,), jnp.int32, one_chip)],
        _sds((), jnp.int32, one_chip))
    probe = _batch([_col(long, jnp.int64, Q12_PART, one_chip)],
                   ["l_orderkey"], one_chip)

    def kernel(bt, probe, keep):
        ids, total = joinops.front_row_ids(keep & probe.live_mask(), cap)
        front = probe.gather(ids, jnp.minimum(total, cap))
        lo, matched, dup = joinops.probe_unique(bt, front, [0])
        got = bt.batch.columns[1].gather(
            jnp.clip(lo, 0, bt.batch.capacity - 1))
        return got.data, got.validity & matched, jnp.any(dup), total > cap

    c = _compile(kernel, build, probe,
                 _sds((Q12_PART,), jnp.bool_, one_chip))
    assert c.memory_analysis().temp_size_in_bytes < (256 << 20)


def test_build_side_sort_is_one_32_bit_operand_at_q12_width(one_chip,
                                                            as_tpu):
    """`joinops.build_side` over SF10 `orders` as buildprep sees it —
    the key's stamped range fits 32 bits, rows live where `live` says:
    ONE sort operand beside the row ids. (The general path's two
    64-bit operands take the compiler two minutes: not compiled here.)"""
    from spark_rapids_tpu.ops import joinops

    key = _col(long, jnp.int64, Q12_BUILD, one_chip, vrange=(0, 2 ** 26 - 1))
    batch = _batch([key, _col(long, jnp.int64, Q12_BUILD, one_chip)],
                   ["o_orderkey", "o_code"], one_chip)
    assert joinops._fits_32_bits(batch, [0])

    def kernel(batch, live):
        bt = joinops.build_side(batch, [0], live)
        return bt.keys[0], bt.batch.columns[1].data, bt.valid_bound

    c = _compile(kernel, batch, _sds((Q12_BUILD,), jnp.bool_, one_chip))
    sorts = [ln for ln in c.as_text().splitlines() if " sort(" in ln]
    assert len(sorts) == 1 and "s64" not in sorts[0].split(" sort(")[0]
    assert c.memory_analysis().temp_size_in_bytes < (1 << 30)


def test_build_index_moves_no_row_at_q12_width(one_chip, as_tpu):
    """`joinops.build_index` over the same `orders`: the one sort, whose
    own outputs are the sorted keys and the permutation — not one of
    `build_side`'s 15.7M-slot gathers (six of them were 1.24 s of
    Q12's 1.85 s: PERF.md, PR 29)."""
    from spark_rapids_tpu.ops import joinops

    key = _col(long, jnp.int64, Q12_BUILD, one_chip, vrange=(0, 2 ** 26 - 1))
    batch = _batch([key, _col(long, jnp.int64, Q12_BUILD, one_chip)],
                   ["o_orderkey", "o_code"], one_chip)

    def kernel(batch, live):
        idx = joinops.build_index(batch, [0], live)
        return idx.keys[0], idx.perm, idx.valid_bound, idx.num_rows

    c = _compile(kernel, batch, _sds((Q12_BUILD,), jnp.bool_, one_chip))
    text = c.as_text()
    sorts = [ln for ln in text.splitlines() if " sort(" in ln]
    assert len(sorts) == 1 and "s64" not in sorts[0].split(" sort(")[0]
    assert " gather(" not in text
    assert c.memory_analysis().temp_size_in_bytes < (256 << 20)


def test_late_lookup_join_at_q12_width(one_chip, as_tpu):
    """The chain's side of a lookup join (exec/fused.py `lookup_join`
    over a BuildIndex): the survivors' row-ids and their one search,
    a row of 128 keys a level, then the build columns read at
    `perm[lo]` from the batch as it lies — three gathers of 122,880
    slots where the build side had six of 15.7M."""
    from spark_rapids_tpu.exec.fused import survivor_capacity
    from spark_rapids_tpu.ops import joinops

    cap = survivor_capacity(Q12_PART)
    build = joinops.BuildIndex(
        _batch([_col(long, jnp.int64, Q12_BUILD, one_chip),
                _col(long, jnp.int64, Q12_BUILD, one_chip)],
               ["o_orderkey", "o_code"], one_chip),
        [_sds((Q12_BUILD,), jnp.int32, one_chip)],
        _sds((Q12_BUILD,), jnp.int32, one_chip),
        _sds((), jnp.int32, one_chip), _sds((), jnp.int32, one_chip))
    probe = _batch([_col(long, jnp.int64, Q12_PART, one_chip)],
                   ["l_orderkey"], one_chip)

    def kernel(bt, probe, keep):
        ids, total = joinops.front_row_ids(keep & probe.live_mask(), cap)
        front = probe.gather(ids, jnp.minimum(total, cap))
        lo, matched, dup = joinops.probe_unique(bt, front, [0])
        rows, plain_read = joinops.rows_at(
            bt, jnp.clip(lo, 0, bt.capacity - 1))
        got = bt.batch.columns[1].gather(rows)
        # as the chain returns them: the flags, then what nothing reads
        return (got.data, got.validity & matched,
                jnp.stack([jnp.any(dup), total > cap, plain_read]))

    c = _compile(kernel, build, probe,
                 _sds((Q12_PART,), jnp.bool_, one_chip))
    assert c.memory_analysis().temp_size_in_bytes < (256 << 20)
    _searches_read_rows(c.as_text())


Q5_PART = Q12_PART        # `lineitem`'s parts, read at full width
Q5_BUILD = Q12_BUILD      # `orders` under `customer`, 8 parts end to end


def _q5_build(one_chip):
    """The derived build side of TPC-H Q5's `lineitem` join as buildprep
    sees it: `o_orderkey`, stamped to fit 32 bits, and `c_nationkey`,
    the one column the chain reads."""
    return _batch([_col(long, jnp.int64, Q5_BUILD, one_chip,
                        vrange=(0, 2 ** 26 - 1)),
                   _col(long, jnp.int64, Q5_BUILD, one_chip,
                        vrange=(0, 31))],
                  ["o_orderkey", "c_nationkey"], one_chip)


def test_build_index_in_index_order_sorts_one_pair_at_q5_width(one_chip,
                                                               as_tpu):
    """`joinops.build_index` carrying the column the join reads, coded
    as one 32-bit array (`DeviceColumn.coded`), in place of the row
    ids: still ONE sort of two 32-bit operands, whose outputs are the
    sorted keys and the column in their order, and no 15.7M-slot
    gather."""
    from spark_rapids_tpu.ops import joinops

    batch = _q5_build(one_chip)
    assert batch.columns[1].reads_as_one_array()

    def kernel(batch, live):
        idx = joinops.build_index(batch, [0], live,
                                  [batch.columns[1].coded()])
        assert idx.perm is None
        return idx.keys[0], idx.coded[0], idx.valid_bound, idx.num_rows

    c, lowered, _ = _timed_compile(kernel, batch,
                                   _sds((Q5_BUILD,), jnp.bool_, one_chip))
    assert _sort_operands(lowered) == [2]
    text = c.as_text()
    sorts = [ln for ln in text.splitlines() if " sort(" in ln]
    assert len(sorts) == 1 and "s64" not in sorts[0].split(" sort(")[0]
    assert " gather(" not in text
    assert c.memory_analysis().temp_size_in_bytes < (256 << 20)


def test_late_lookup_join_in_index_order_at_q5_width(one_chip, as_tpu):
    """The chain's side over that index, at a part's full width: the
    search, then `c_nationkey` read at `lo` itself — ONE element a slot
    where `perm[lo]` and then the column were two (541 + 449 ms of
    Q5's query: PERF.md section 5) — and the plain read of the moved
    column that `rows_at` makes of the permutation (`coded_at`). The
    other element read is the key after each match, in the branch
    taken only where the index holds a key twice."""
    from spark_rapids_tpu.ops import joinops

    batch = _q5_build(one_chip)
    build = joinops.BuildIndex(
        batch, [_sds((Q5_BUILD,), jnp.int32, one_chip)], None,
        _sds((), jnp.int32, one_chip), _sds((), jnp.int32, one_chip),
        coded=(None, _sds((Q5_BUILD,), jnp.int32, one_chip)))
    probe = _batch([_col(long, jnp.int64, Q5_PART, one_chip)],
                   ["l_orderkey"], one_chip)

    def kernel(bt, probe):
        lo, matched = joinops.probe_matched(bt, probe, [0])
        dup = joinops.second_match(bt, probe, [0], lo, matched)
        got, plain_read = joinops.coded_at(
            bt, jnp.clip(lo, 0, bt.capacity - 1))
        col = bt.batch.columns[1].decoded(got[1])
        # as the chain returns them: the flags, then what nothing reads
        return (col.data, col.validity & matched,
                jnp.stack([jnp.any(dup), plain_read]))

    c, _, _ = _timed_compile(kernel, build, probe)
    lines = c.as_text().splitlines()
    entry = lines[next(i for i, ln in enumerate(lines)
                       if ln.startswith("ENTRY")):]
    params = [ln.split(" = ")[0].strip() for ln in entry
              if " parameter(" in ln and f"s32[{Q5_BUILD}]" in ln]
    assert len(params) == 2          # the keys and the moved column
    elements = [ln for ln in lines if " gather(" in ln
                and "slice_sizes={1}" in ln
                and f"[{Q5_PART}]" in ln.split(" gather(")[0]]
    assert len(elements) == 2, elements
    assert sum(" conditional(" in ln for ln in lines) == 2
    plain = [ln for ln in entry if " slice(" in ln
             and ln.split(" = ")[1].startswith("s32[1]")
             and any(f"slice({p})" in ln for p in params)]
    assert len(plain) == 1, plain
    assert c.memory_analysis().temp_size_in_bytes < (1 << 30)


def _searches_read_rows(text):
    """What a Q12 chain program's time hangs on since PR 34, at SF10's
    widths: the survivors' row-ids and the one search of the build
    keys descend a tree of 128-key rows (joinops._count_below), ONE
    `slice_sizes={1,128}` read a level, and no loop re-reads the 15.7M
    keys a key a step (168.5 + 161.4 ms of a 509 ms query were two such
    loops: PERF.md, PR 34). Where the compiler keeps the keys no longer
    matters: no placement is asserted."""
    from spark_rapids_tpu.ops import joinops

    lines = text.splitlines()
    assert not [ln for ln in lines
                if " while(" in ln and f"s32[{Q12_BUILD}]" in ln]
    gathers = [ln.split(" = ")[1] for ln in lines if " gather(" in ln]
    rows = [g for g in gathers if "slice_sizes={1,128}" in g]
    assert len(rows) == (joinops.search_reads(Q12_PART)
                         + joinops.search_reads(Q12_BUILD))
    assert all(g.startswith("s32[122880,128]") for g in rows)
    # every other gather reads one element a survivor; of 32-bit
    # integers that is the build key at `lo` and at `lo + 1` and the
    # permutation at `lo` — the row-ids read none
    assert all("slice_sizes={1}" in g for g in gathers if g not in rows)
    assert len([g for g in gathers
                if g not in rows and g.startswith("s32[")]) <= 3


@pytest.fixture(scope="module")
def q12_chain():
    """`tpch_q12_join_resident`'s own chain program — filter, the
    survivors' lookup join, partial aggregate — as a rehearsal of the
    cell at 120,000 rows traces it on this CPU: (the function the
    engine hands to jit, the inputs of one call)."""
    from benchmark import run
    from spark_rapids_tpu.runtime import jit_cache

    seen = []
    real = jit_cache.cached_jit

    def spy(key, build, **kw):
        jitted = real(key, build, **kw)

        def call(*inputs):
            if key[1] == "chain":
                seen.append((build(), inputs))
            return jitted(*inputs)
        return call

    from spark_rapids_tpu.exec import joins

    mp = pytest.MonkeyPatch()
    mp.setattr(jit_cache, "cached_jit", spy)
    mp.setattr(run, "session_conf",  # the tests' compile cache, not
               lambda config: dict(config["session_conf"]))  # benchmark/'s
    # at SF10 `o_orderkey`'s 67M values are far more than the 983,040
    # survivors x 24 steps that probe them, and the build side keeps its
    # sorted index; the rehearsal's 30,000 orders would get a table of
    # positions (exec/fused.py `build_table`), which is not the program
    # the cell runs: no key range here, as at SF10's sizes
    mp.setattr(joins.TpuBroadcastHashJoinExec, "build_key_range",
               lambda self, right: None)
    try:
        res = run.run_cell("tpch_q12_join_resident", 2_147_483_777, 0.1,
                           False, rows=120_000, any_platform=True)
    finally:
        mp.undo()
    assert res["correct"] and seen
    return seen[-1]


@pytest.fixture(scope="module")
def q12_chain_at_sf10(q12_chain, one_chip, no_persistent_cache):
    """The program the cell runs, lowered again at SF10's widths and
    compiled for the described chip: (its text, the seconds that
    took)."""
    from spark_rapids_tpu.exec.fused import survivor_capacity

    fn, (probe, build) = q12_chain
    (jp,) = fn.__kwdefaults__["_plan"]
    assert (jp["lowering"], jp["buildGather"]) == ("lookupSurvivors",
                                                   "matched")
    cap = survivor_capacity(Q12_PART)
    at_sf10 = type(fn)(fn.__code__, fn.__globals__, fn.__name__,
                       fn.__defaults__, fn.__closure__)
    at_sf10.__kwdefaults__ = dict(fn.__kwdefaults__, _plan=[dict(
        jp, probeSlots=Q12_PART, searchedSlots=cap, outputCapacity=cap,
        buildSlots=Q12_BUILD)])

    def widened(tree, small, slots):
        return jax.tree_util.tree_map(
            lambda a: _sds(tuple(slots if d == small else d
                                 for d in a.shape), a.dtype, one_chip), tree)

    c, _, seconds = _timed_compile(
        at_sf10, widened(probe, probe.capacity, Q12_PART),
        widened(build, build.capacity, Q12_BUILD))
    assert c.memory_analysis().temp_size_in_bytes < (256 << 20)
    return c.as_text(), seconds


def test_q12s_own_chain_searches_a_row_a_level(q12_chain_at_sf10):
    """The cell's own chain program at SF10's widths holds the row
    reads of `_searches_read_rows` and nothing of the two loops, and
    the compiler takes it in seconds (each loop's program compiled
    slower than the tree that replaced it)."""
    text, seconds = q12_chain_at_sf10
    _searches_read_rows(text)
    assert seconds < 60


def test_q12s_own_chain_scatters_no_row(q12_chain_at_sf10):
    """Its partial aggregate — two `sum(case when … then 1 else 0
    end)` into l_shipmode's bins — rides the MXU in 8-bit limbs
    (ops/segmented.py `_mm_sum_plan`): the two 64-bit scatter-adds
    over the survivors' 122,880 slots (61.5 ms each a query; PERF.md,
    PR 32) are gone, and the one scatter left moves no row: it
    brings the occupied bins to the front (`dense_bin_perm`, 1,024
    slots of 32 bits)."""
    text, _ = q12_chain_at_sf10
    scatters = [ln.split(" = ")[1] for ln in text.splitlines()
                if " scatter(" in ln]
    assert len(scatters) == 1 and scatters[0].startswith("s32[1024]")
    # the sweep: one loop whose carries are the bins, 32,768 rows a step
    assert [ln for ln in text.splitlines()
            if " while(" in ln and "f32[4,32768]" in ln]


# ------------------------------------- the star's own programs (q3)

STAR_PART = 3_670_016     # slots of one of SF10 store_sales' 8 parts
STAR_ROWS = 2_000_000     # the rehearsal: parts of 262,144 slots, the
#                           least at which both joins place their bets


@pytest.fixture(scope="module")
def q3_programs():
    """TPC-DS q3 as `tpcds_star_resident` runs it, traced on this CPU
    at 2M fact rows: {kind: (the function the engine hands to jit, the
    inputs of one call)} of its SETTLED run — the second one, in which
    the date join, having lost its bet (d_moy = 11 keeps 8% of the
    fact), has yielded to the item join."""
    import tempfile

    from benchmark import run
    from spark_rapids_tpu.api.session import TpuSparkSession
    from spark_rapids_tpu.runtime import jit_cache

    seen = {}
    real = jit_cache.cached_jit

    def spy(key, build, **kw):
        jitted = real(key, build, **kw)

        def call(*inputs):
            # the chain with the joins, not the 1-part one that only
            # renames the final aggregate's columns
            if key[0] == "fused" and (key[1] != "chain"
                                      or len(inputs) > 1):
                seen[key[1]] = (build(), inputs)
            return jitted(*inputs)
        return call

    conf = run.load_json(run.HERE, "configs",
                         "tpcds_sf10_store_sales_star.json")
    gen = run.load_module("datagen", conf["generator"])
    mp = pytest.MonkeyPatch()
    mp.setattr(jit_cache, "cached_jit", spy)
    spark = TpuSparkSession(dict(conf["session_conf"]))
    try:
        with tempfile.TemporaryDirectory() as tmp:
            dirs = gen.generate(conf, 2_147_483_777, tmp, rows=STAR_ROWS)
            tables = {t: spark.read.parquet(d).cache(storage="device")
                      for t, d in dirs.items()}
            q3 = run.load_module("queries", "tpcds_spec_q3")
            df = q3.build(spark, tables)
            df.collect_arrow()
            assert spark.last_execution["join"]["rerunReasons"] == [
                "survivorOverflow"]
            seen.clear()
            df.collect_arrow()
            rec = spark.last_execution
    finally:
        spark.stop()
        mp.undo()
    assert run.not_fused(rec) == "" and rec["join"]["runs"] == 1
    # the item join first (93 of its 102,000 rows pass), then the date
    assert [j["buildRows"] < 1_000 for j in rec["join"]["joins"]] == [
        True, False]
    assert [j["bet"] for j in rec["join"]["joins"]] == ["buildFilter"] * 2
    return seen


def _sort_operands(lowered_text):
    """Operands of every sort a program hands to XLA, from its lowered
    (StableHLO) text: a key and the permutation are 2. (The TPU's
    compiler adds an operand of its own to a stable sort.)"""
    import re

    return [len(m.group(1).split(","))
            for m in re.finditer(r'"stablehlo.sort"\(([^)]*)\)',
                                 lowered_text)]


def _timed_compile(fn, *avals):
    """-> (compiled, its lowered text, seconds of lower + compile)."""
    import time

    t = time.perf_counter()
    with pytest.MonkeyPatch.context() as mp:  # `as_tpu`, module-wide
        mp.setattr(jax, "default_backend", lambda: "tpu")
        lowered = jax.jit(fn).lower(*avals)
        c = lowered.compile()
    return c, lowered.as_text(), time.perf_counter() - t


def test_q3s_own_chain_at_sf10_sorts_one_operand_and_probes_by_position(
        q3_programs, one_chip, no_persistent_cache):
    """The chain program — both positional probes, both bets, the
    3-key partial aggregate — at SF10's widths: a part of 3,670,016
    slots, the item join's matches at 57,344, the date join's at 1,024.
    The parent's could not be compiled in 37 minutes (PERF.md, PR 23:
    one sort on seven operands); this one holds ONE sort on one key
    operand and the permutation, a loop of two passes over 1,024
    slots, and compiles in seconds. At the rehearsal's size the date
    join searches a sorted index (a table of 4,194,304 positions for
    2,097,152 probe slots is not worth writing): the build side is
    given here as the table SF10's run makes."""
    from spark_rapids_tpu.exec.fused import survivor_capacity
    from spark_rapids_tpu.ops import joinops

    fn, (probe, *builds) = q3_programs["chain"]
    plan = fn.__kwdefaults__["_plan"]
    small = probe.capacity
    dims = {small: STAR_PART,
            survivor_capacity(small): survivor_capacity(STAR_PART)}
    assert dims == {262_144: 3_670_016, 4_096: 57_344}
    at_sf10 = type(fn)(fn.__code__, fn.__globals__, fn.__name__,
                       fn.__defaults__, fn.__closure__)
    at_sf10.__kwdefaults__ = dict(fn.__kwdefaults__, _plan=[
        dict(jp, probe="position", probeSteps=1,
             **{k: dims.get(jp[k], jp[k]) for k in
                ("probeSlots", "searchedSlots", "outputCapacity")})
        for jp in plan])
    assert [jp["outputCapacity"] for jp in
            at_sf10.__kwdefaults__["_plan"]] == [57_344, 1_024]

    def sds(tree):
        return jax.tree_util.tree_map(
            lambda a: _sds(tuple(dims.get(d, d) for d in a.shape), a.dtype,
                           one_chip), tree)

    def positions(bt):
        (key,) = [c for c, f in zip(bt.batch.columns,
                                    bt.batch.schema.fields)
                  if f.name in ("i_item_sk", "d_date_sk")]
        size = key.vrange[1] - key.vrange[0] + 1
        scalar = _sds((), jnp.int32, one_chip)
        return joinops.BuildPositions(
            sds(bt.batch), _sds((size,), jnp.int32, one_chip),
            _sds((), jnp.int64, one_chip), scalar, scalar)

    tables = [positions(bt) for bt in builds]
    assert [t.table.shape[0] for t in tables] == [131_072, 4_194_304]
    c, lowered, seconds = _timed_compile(at_sf10, sds(probe), *tables)
    assert _sort_operands(lowered) == [2]  # one key, and the permutation
    (sort,) = [ln for ln in c.as_text().splitlines() if " sort(" in ln]
    assert "u32[1024]" in sort and "[57344]" not in sort
    # both bets' row ids read a row of the mask's prefix sum a level
    # (the part's 3,670,016 slots, then the 57,344 that were left), both
    # probes read a row of their table of positions a slot, and so does
    # each of the three build columns the chain reads (`i_brand_id`,
    # `i_brand`'s codes, `d_year`: DeviceColumn.gather through
    # joinops.read_entries)
    assert [joinops.table_rows(t.table.shape[0]) for t in tables] == [
        1_024, 32_768]
    assert len([ln for ln in c.as_text().splitlines()
                if " gather(" in ln and "slice_sizes={1,128}" in ln]) == (
        joinops.search_reads(STAR_PART) + joinops.search_reads(57_344)
        + len(tables) + 3)
    assert seconds < 30, seconds         # 3.0-3.9 s when written
    assert c.memory_analysis().temp_size_in_bytes < (256 << 20)


@pytest.mark.parametrize("kind, limit_s", [("agg", 15), ("sort", 15)])
def test_q3s_own_final_aggregate_and_sort_compile_in_seconds(
        q3_programs, one_chip, no_persistent_cache, kind, limit_s):
    """The final aggregate over the 8 parts' 1,024 slots each and the
    ORDER BY over its 8,192 (d_year, the sum descending, brand_id: an
    int, an f32-ordered double and an int): the shapes ARE SF10's,
    since the bets cut every part to the same 1,024 slots. Each is one
    loop of one-operand passes; the parent's compiled for 1,606 s and
    1,029 s (sandbox, PERF.md section 7, finding 1, at PR 23)."""
    fn, inputs = q3_programs[kind]
    sds = jax.tree_util.tree_map(
        lambda a: _sds(a.shape, a.dtype, one_chip), inputs)
    c, lowered, seconds = _timed_compile(fn, *sds)
    operands = _sort_operands(lowered)
    assert operands and max(operands) == 2
    assert seconds < limit_s, seconds    # 1.3-1.8 s and 0.9-1.1 s


# ------------------------- TPC-H Q3's own chain (customer, orders, lineitem)

H3_PART = 7_864_320       # slots of one of SF10 lineitem's 8 parts
H3_BUILD = 15_728_640     # `orders` under `customer`: 8 parts of 1,966,080
H3_ROWS = 1_000_000       # the rehearsal: parts of 131,072 slots


@pytest.fixture(scope="module")
def tpch_q3_chain():
    """`tpch_q3_join_resident`'s big chain program — `lineitem`'s
    filter, the shuffled join to the derived build side (`orders` under
    the segment's customers) at full width, the bet on its matches, the
    3-key partial aggregate — as a hot query of a 1,000,000-row
    rehearsal traces it on this CPU: (the function the engine hands to
    jit, the inputs of one call)."""
    from benchmark import run
    from spark_rapids_tpu.exec import joins
    from spark_rapids_tpu.ops import joinops
    from spark_rapids_tpu.runtime import jit_cache

    seen = []
    real = jit_cache.cached_jit

    def spy(key, build, **kw):
        jitted = real(key, build, **kw)

        def call(*inputs):
            if key[1] == "chain" and any(
                    isinstance(i, joinops.BuildIndex) for i in inputs):
                seen.append((build(), inputs))
            return jitted(*inputs)
        return call

    def holds_a_join(node):
        return isinstance(node, joins._DeviceJoinBase) or any(
            holds_a_join(c) for c in node.children)

    range_of = joins._DeviceJoinBase.build_key_range
    mp = pytest.MonkeyPatch()
    mp.setattr(jit_cache, "cached_jit", spy)
    mp.setattr(run, "session_conf",
               lambda config: dict(config["session_conf"]))
    # at SF10 a table of `o_orderkey`'s 60M values would take 58 chunks
    # of 15.7M offers each and the derived build side keeps its sorted
    # index (exec/fused.py `build_table`); the rehearsal's 1M values are
    # one chunk: no key range there, as at SF10's sizes
    mp.setattr(joins._DeviceJoinBase, "build_key_range",
               lambda self, right: None if holds_a_join(self.children[1])
               else range_of(self, right))
    try:
        res = run.run_cell("tpch_q3_join_resident", 2_147_483_777, 0.1,
                           False, rows=H3_ROWS, any_platform=True)
    finally:
        mp.undo()
    assert res["correct"] and seen
    return seen[-1]


def test_tpch_q3s_own_chain_at_sf10_searches_a_row_a_level_at_full_width(
        tpch_q3_chain, one_chip, no_persistent_cache):
    """The program the cell spends its time in, lowered again at SF10's
    widths: 7,864,320 probe slots search 15,728,640 build slots a row
    of 128 keys a level (3 reads, in blocks of 131,072 probes; 2, of
    a window of the index, where a block's keys lie close), the
    matches' row ids descend the mask's prefix sum the same way, the
    122,880 slots they are brought to carry every later gather, and no
    buffer of the program has the 2^28 slots an expanding join would
    ask for (PERF.md section 6, PR 35)."""
    from spark_rapids_tpu.exec.fused import survivor_capacity
    from spark_rapids_tpu.ops import joinops

    fn, (probe, build) = tpch_q3_chain
    (jp,) = fn.__kwdefaults__["_plan"]
    assert (jp["lowering"], jp["bet"], jp["probe"]) == (
        "lookupSurvivors", "buildFilter", "search")
    assert jp["searchedSlots"] == jp["probeSlots"] == probe.capacity
    dims = {probe.capacity: H3_PART, build.capacity: H3_BUILD,
            survivor_capacity(probe.capacity): survivor_capacity(H3_PART)}
    # (a rehearsal's files are under the million rows from which a PLAIN
    # file's batch takes its bucket: capacity == rows, 8 x 125,000)
    assert len(dims) == 3 and dims[2_048] == 122_880
    # the stamped ranges of SF10's keys: 60M order keys, 1.5M customers
    ranges = {(0, 1_048_575): (0, 67_108_863), (0, 32_767): (0, 2_097_151)}
    at_sf10 = type(fn)(fn.__code__, fn.__globals__, fn.__name__,
                       fn.__defaults__, fn.__closure__)
    at_sf10.__kwdefaults__ = dict(fn.__kwdefaults__, _plan=[dict(
        {k: dims.get(v, v) if type(v) is int else v for k, v in jp.items()},
        # the rehearsal's 125,000 probes fit one block; a part's do not
        searchBlocks=joinops.search_blocks(H3_PART))])

    def widened(tree):
        def column(c):
            if not isinstance(c, DeviceColumn):
                return _sds(tuple(dims.get(d, d) for d in c.shape), c.dtype,
                            one_chip)
            leaves, aux = c._tree_flatten()
            aux = aux[:4] + (ranges.get(aux[4], aux[4]),) + aux[5:]
            return DeviceColumn._tree_unflatten(
                aux, [widened(leaf) for leaf in leaves])
        return jax.tree_util.tree_map(
            column, tree, is_leaf=lambda x: isinstance(x, DeviceColumn))

    c, _, seconds = _timed_compile(at_sf10, widened(probe), widened(build))
    text = c.as_text()
    lines = text.splitlines()
    assert "268435456" not in text and "67108864" not in text
    assert not [ln for ln in lines
                if " while(" in ln and f"s32[{H3_BUILD}]" in ln
                and "128]" not in ln]
    rows = [ln for ln in lines
            if " gather(" in ln and "slice_sizes={1,128}" in ln]

    def table_of(ln):
        """Shape of the table a gather's line reads: its first operand,
        as the lines above define it."""
        name = ln.split(" gather(")[1].split(",")[0]
        return next(above.split(" = ")[1].split("{")[0]
                    for above in reversed(lines[:lines.index(ln)])
                    if above.strip().startswith(name + " = "))

    def reads(where, width):
        return sorted(table_of(ln) for ln in rows
                      if where in ln and f"[{width},128]" in
                      ln.split(" gather(")[0])

    # the key's search runs a block of 131,072 probes at a time. A
    # block first descends the whole index for its smallest and its
    # largest key, 3 reads each
    level = f"s32[{H3_BUILD // 128},128]"
    whole = sorted(["s32[8,128]", "s32[960,128]", level])
    assert reads("while/body", 2) == whole
    # then takes one of two branches. Where they lie far apart, the
    # whole index again, a row a level
    assert reads("cond/branch_0_fun", 131_072) == whole
    # where they lie close (a probe side clustered by its key), a
    # window of 1,024 rows cut from the bottom level and the 8 rows of
    # its last keys, both small enough for fast memory, and NEVER the
    # 63 MB level itself: 17.4 ns a row there, 1,095 of the query's
    # 2,890 device ms (PERF.md section 6, PR 36)
    window = f"s32[{joinops._WINDOW_ROWS},128]"
    assert reads("cond/branch_1_fun", 131_072) == sorted(
        ["s32[8,128]", window])
    (cut,) = [ln for ln in lines if " dynamic-slice(" in ln
              and ln.strip().startswith("ROOT")
              and f" {window}" in ln.split(" dynamic-slice(")[0]]
    assert "S(1)" in cut.split(" dynamic-slice(")[0]
    # the row ids of the matches descend the mask's prefix sum at their
    # own capacity, outside the loop
    assert len(reads("", 122_880)) == joinops.search_reads(H3_PART) == 3
    assert len(rows) == 2 * joinops.search_reads(H3_BUILD) + 2 + 3
    # the search's two branches, and the key after a match read only
    # where the index holds a key twice (joinops.second_match)
    assert sum(" conditional(" in ln for ln in lines) == 2
    # whether a probe matched is read off the row the search fetched
    # last, and the key after a match is read at the survivors' width:
    # no gather of one element a full-width slot (27 ns a slot, 1.7 s a
    # query each of the two: PERF.md section 6, PR 35)
    assert not [ln for ln in lines if " gather(" in ln
                and f"[{H3_PART}]" in ln.split(" gather(")[0]]
    # one sort, the group-by's, over the survivors' slots only
    (sort,) = [ln for ln in lines if " sort(" in ln]
    assert "[122880]" in sort
    assert seconds < 120, seconds        # 33 s when written (sandbox)
    assert c.memory_analysis().temp_size_in_bytes < (1 << 30)


# ------------- TPC-H Q5's own chain (lineitem into four joins, one packed)

H5_TABLE = 131_072 * 32   # SF10's packed (s_suppkey, s_nationkey) range
H5_SUPPLIERS = 100_000    # one PLAIN file: capacity == rows


@pytest.fixture(scope="module")
def tpch_q5_chain():
    """`tpch_q5_join_resident`'s big chain program — `lineitem`'s search
    of the customers' orders at full width, the supplier join on two
    keys packed into one and probed by position, `nation`, `region`'s
    bet, the partial aggregate — as a hot query of a 1,000,000-row
    rehearsal traces it on this CPU: (the function the engine hands to
    jit, the inputs of one call)."""
    from benchmark import run
    from spark_rapids_tpu.ops import joinops
    from spark_rapids_tpu.runtime import jit_cache

    seen = []
    real = jit_cache.cached_jit

    def spy(key, build, **kw):
        jitted = real(key, build, **kw)

        def call(*inputs):
            if key[1] == "chain" and any(
                    isinstance(i, joinops.BuildPositions) and i.packing
                    for i in inputs):
                seen.append((build(), inputs))
            return jitted(*inputs)
        return call

    mp = pytest.MonkeyPatch()
    mp.setattr(jit_cache, "cached_jit", spy)
    mp.setattr(run, "session_conf",
               lambda config: dict(config["session_conf"]))
    try:
        res = run.run_cell("tpch_q5_join_resident", 2_147_483_777, 0.1,
                           False, rows=H3_ROWS, any_platform=True)
    finally:
        mp.undo()
    assert res["correct"] and seen
    return seen[-1]


def test_tpch_q5s_own_chain_at_sf10_reads_two_columns_an_element_a_slot(
        tpch_q5_chain, one_chip, no_persistent_cache):
    """The program Q5 spends its time in, lowered again at SF10's
    widths: of everything its four joins read at a part's full width
    (7,864,320 slots), only the ONE 32-bit array `c_nationkey` is a
    gather of an element a slot (from a 15.7M-slot array: too large
    to be read as rows), at `lo` itself: the index carries it in its
    own order (`buildOrder: index`), where the row ids at `lo` and
    then the column were two such gathers, 541 + 449 ms of a query
    (PERF.md section 5). The two supplier keys are packed into one and read by
    position, a row of the 4,194,304-entry table a slot, and every
    column of `supplier` and `nation` a later join reads is a row of
    128 entries a slot: the first tree read 13 such element gathers, 7
    of them 64-bit halves and validity, and the key after each match,
    9,293 ms a query (PERF.md section 6); that key is now read only
    where the index holds a key twice."""
    from spark_rapids_tpu.exec.fused import survivor_capacity
    from spark_rapids_tpu.ops import joinops

    fn, (probe, index, suppliers, nation, region) = tpch_q5_chain
    plan = fn.__kwdefaults__["_plan"]
    assert [(jp["probe"], jp["bet"]) for jp in plan] == [
        ("search", ""), ("position", ""), ("position", ""),
        ("position", "buildFilter")]
    assert [jp["buildOrder"] for jp in plan] == ["index"] + ["asLies"] * 3
    assert index.perm is None
    assert [a is not None for a in index.coded].count(True) == 1
    assert suppliers.packing.ranges[1] == (0, 31)
    small, built = probe.capacity, index.capacity
    dims = {small: H3_PART, built: H3_BUILD,
            suppliers.batch.capacity: H5_SUPPLIERS,
            suppliers.table.shape[0]: H5_TABLE,
            survivor_capacity(small): survivor_capacity(H3_PART)}
    assert len(dims) == 5
    keys = {suppliers.packing.ranges[0]: (0, 131_071),
            (0, 1_048_575): (0, 67_108_863), (0, 32_767): (0, 2_097_151)}
    at_sf10 = type(fn)(fn.__code__, fn.__globals__, fn.__name__,
                       fn.__defaults__, fn.__closure__)
    at_sf10.__kwdefaults__ = dict(fn.__kwdefaults__, _plan=[dict(
        {k: dims.get(v, v) if type(v) is int else v for k, v in jp.items()},
        searchBlocks=joinops.search_blocks(H3_PART)
        if jp["probe"] == "search" else 0) for jp in plan])

    def widened(tree, dims):
        def column(c):
            if not isinstance(c, DeviceColumn):
                return _sds(tuple(dims.get(d, d) for d in c.shape), c.dtype,
                            one_chip)
            leaves, aux = c._tree_flatten()
            vr = keys.get(aux[4], aux[4])
            new = [widened(leaf, dims) for leaf in leaves]
            if vr == (0, 131_071) and new[0].dtype == jnp.int16:
                # SF10's supplier keys no longer fit the 16-bit upload
                new[0] = _sds(new[0].shape, jnp.int32, one_chip)
            return DeviceColumn._tree_unflatten(
                aux[:4] + (vr,) + aux[5:], new)
        return jax.tree_util.tree_map(
            column, tree, is_leaf=lambda x: isinstance(x, DeviceColumn))

    inputs = [widened(probe, {small: H3_PART}),
              widened(index, {built: H3_BUILD}),
              widened(suppliers, dims)._replace(
                  packing=joinops.KeyPacking(((0, 131_071), (0, 31)))),
              widened(nation, {}), widened(region, {})]
    assert inputs[2].packing.entries == H5_TABLE
    c, _, seconds = _timed_compile(at_sf10, *inputs)
    lines = c.as_text().splitlines()
    elements = [ln for ln in lines if " gather(" in ln
                and f"[{H3_PART}]" in ln.split(" gather(")[0]]
    # the second is the key after each match, in a branch taken only
    # where the index holds a key twice (joinops.second_match): the
    # orders of SF10 hold none. The other conditional is the search's
    assert len(elements) == 2, elements
    assert sum(" conditional(" in ln for ln in lines) == 2
    rows = [ln for ln in lines if " gather(" in ln
            and "slice_sizes={1,128}" in ln]
    tables = {ln.split(" gather(")[0].split("=")[1].split("{")[0].strip()
              for ln in rows}
    # rows of the suppliers' table and of their columns, 8,192 slots a
    # block (joinops._PICK_BLOCK)
    assert "s32[8192,128]" in tables
    assert seconds < 120, seconds        # 8-10 s when written, on a CPU host
    assert c.memory_analysis().temp_size_in_bytes < (1 << 30)


def test_expanded_join_gather_maps(one_chip, as_tpu):
    """The dup-key join's blocking lowering: (lo, counts) expanded to
    probe/build gather maps at a static output capacity (2 matches per
    probe row -> 2^24 slots for one partition), both sides gathered."""
    from spark_rapids_tpu.ops import joinops

    out_cap = 1 << 24

    def kernel(bt, probe):
        lo, counts = joinops.probe_ranges(bt, probe, [0])
        pi, bi, total = joinops.expand_gather_maps(lo, counts, out_cap)
        return (probe.columns[1].gather(pi).data,
                bt.batch.columns[1].gather(bi).data, total)

    c = _compile(kernel, _sorted_build(one_chip), _probe(one_chip))
    assert c.memory_analysis().temp_size_in_bytes < (4 << 30)


# ------------------------------------------------ murmur3 partitioning

@pytest.mark.parametrize("key", ["int64", "float64"])
def test_murmur3_partition_ids(one_chip, as_tpu, key):
    """Spark-compatible murmur3 + pmod over 64-bit keys; the float64
    key takes the `supports_64bit_bitcast() == False` detour."""
    from spark_rapids_tpu.ops import common, partition

    assert not common.supports_64bit_bitcast()
    col = (_col(long, jnp.int64, ROWS, one_chip) if key == "int64"
           else _col(double, jnp.float64, ROWS, one_chip))
    batch = _batch([col], ["k"], one_chip)
    _compile(lambda b: partition.hash_partition_ids(b, [0], 8), batch)


def test_float64_sort_keys_take_the_f32_detour(one_chip, as_tpu):
    """Orderable keys of a DoubleType column (sort / group / join
    keys): f32 total-order bits on a TPU (ops/common.py)."""
    from spark_rapids_tpu.ops import common

    col = _col(double, jnp.float64, ROWS, one_chip)
    live = _sds((ROWS,), jnp.bool_, one_chip)
    _compile(lambda c, lv: common.orderable_keys(c, True, True, lv),
             col, live)


# ------------------------------------------------------ widen_traced

def test_widen_traced_narrowed_scan_batch(one_chip, as_tpu):
    """The bench's fact partition as uploaded (store int16, qty int8,
    day int16, amount f64) widened back to int64 in-trace and consumed
    (exec/fused.py upload_narrowed / widen_traced)."""
    from spark_rapids_tpu.exec.fused import widen_traced

    cols = [_col(long, jnp.int16, ROWS, one_chip, vrange=(0, 2047)),
            _col(double, jnp.float64, ROWS, one_chip),
            _col(long, jnp.int8, ROWS, one_chip, vrange=(0, 127)),
            _col(long, jnp.int16, ROWS, one_chip, vrange=(0, 511))]
    batch = _batch(cols, ["store", "amount", "qty", "day"], one_chip)

    def kernel(b):
        w = widen_traced(b)
        assert all(c.data.dtype == jnp.int64
                   for c in (w.columns[0], w.columns[2], w.columns[3]))
        revenue = w.columns[1].data * w.columns[2].data
        return jnp.sum(jnp.where(w.live_mask(), revenue, 0.0)), \
            jnp.max(w.columns[0].data + w.columns[3].data)

    _compile(kernel, batch)


# ------------------------------------------------ 4-device all-to-all

def test_all_to_all_batch_on_four_chips(topo, as_tpu):
    """The mesh engine's exchange (parallel/collective.py) as one SPMD
    program over the four described chips: an all-to-all is in the
    compiled text and each device holds its quarter, not the whole."""
    from spark_rapids_tpu.ops import partition
    from spark_rapids_tpu.parallel import collective, mesh_exec
    from spark_rapids_tpu.shims import get_shim

    n = len(topo.devices)
    assert n == 4
    mesh = Mesh(topo.devices, (mesh_exec.AXIS,))
    rows = NamedSharding(mesh, P(mesh_exec.AXIS))
    total = n * A2A_SHARD
    batch = ColumnBatch(
        StructType([StructField("store", long, True),
                    StructField("amount", double, True)]),
        [_col(long, jnp.int64, total, rows),
         _col(double, jnp.float64, total, rows)],
        _sds((n,), jnp.int32, rows))
    slot = collective.slot_capacity(A2A_SHARD, n)

    def step(shard):
        pid = partition.hash_partition_ids(shard, [0], n)
        out, ovf = collective.all_to_all_batch(shard, pid, n, slot,
                                               mesh_exec.AXIS)
        out = ColumnBatch(out.schema, out.columns,
                          jnp.asarray(out.num_rows, jnp.int32).reshape(1))
        return out, ovf.reshape(1)

    spec = mesh_exec.batch_arg_specs(batch, P(mesh_exec.AXIS))
    collective.begin_ici_tape()
    try:
        c = _compile(get_shim().shard_map(
            step, mesh, (spec,),
            (P(mesh_exec.AXIS), P(mesh_exec.AXIS))), batch)
    finally:
        tape = collective.end_ici_tape()
    assert "all-to-all" in c.as_text()
    assert tape and tape[0][1] > 0, "no ICI bytes on the tape"
    per_device = c.memory_analysis().argument_size_in_bytes
    whole = total * (8 + 1 + 8 + 1) + 4 * n
    assert per_device <= whole // n + 1024, (per_device, whole)


def test_a_union_part_padded_to_its_siblings_width_is_a_pad(one_chip,
                                                             as_tpu):
    """TPC-DS q5's `store_returns` part padded to the sales parts'
    3,670,016 slots (`DeviceColumn.padded`, the Union's `fused_pad_*`
    program): pads, no gather. An element gather of every column at
    that width took 1.4 s of a query on the chip (PERF.md, PR 43)."""
    n, to = 2_883_584, 3_670_016
    cols = [_col(long, jnp.int64, n, one_chip, vrange=(0, 127)),
            _col(double, jnp.float64, n, one_chip)]
    batch = _batch(cols, ["store_sk", "sales_price"], one_chip)

    def kernel(b):
        return ColumnBatch(b.schema, [c.padded(to) for c in b.columns],
                           b.num_rows)

    text = _compile(kernel, batch).as_text()
    assert " gather(" not in text and " pad(" in text
    out = jax.eval_shape(kernel, batch)
    assert all(c.data.shape == (to,) and c.validity.shape == (to,)
               for c in out.columns)
    assert out.columns[0].vrange == (0, 127)
