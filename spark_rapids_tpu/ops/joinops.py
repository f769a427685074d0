"""Equi-join kernels: sorted-build search + two-phase gather maps.

cuDF builds device hash tables (`Table.innerJoinGatherMaps`,
`GpuHashJoin.scala:403,490`). HLO has no dynamic hash tables, so the TPU
formulation is a *sort-based* hash join replacement with the same
gather-map contract:

  phase 1 (jit, fixed shape): sort the build side by orderable join keys;
    a vectorized search (a row of 128 keys a level over a one-array
    key, a multi-key binary search else) gives each probe row its
    matching build range [lo, hi) and count. Null join keys never
    match (SQL equi-join semantics) — null-keyed build rows sort to
    the end and are excluded by the live bound; null-keyed probe rows
    are forced to count 0.
  host: read total match count, pick the output capacity bucket.
  phase 2 (jit, fixed shape per bucket): expand (lo, count) into
    (probe_idx, build_idx) gather maps via searchsorted over the count
    prefix sum — the cuDF GatherMap analog — then gather both sides.

This two-phase shape-bucketing is the engine's general answer to
data-dependent output sizes (SURVEY.md section 7 hard part #1/#2).
"""

from __future__ import annotations

from typing import List, NamedTuple, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
from jax import lax

from spark_rapids_tpu.columnar.batch import ColumnBatch
from spark_rapids_tpu.ops.common import (
    equality_keys,
    normalize_floating,
    sort_permutation,
    sorted_with_permutation,
)


class BuildTable(NamedTuple):
    """Build side prepared for probing (device-resident, spillable)."""

    batch: ColumnBatch             # sorted by join keys, null-keyed rows last
    keys: List[jnp.ndarray]        # sorted orderable keys (excl. null rank)
    valid_bound: jnp.ndarray       # scalar int32: rows with non-null keys


class BuildIndex(NamedTuple):
    """Build side indexed for probing and left as it lies: a probe
    that found sorted position `lo` reads the batch's row `perm[lo]`,
    so only the rows a probe matched are ever moved (the fused
    engine's lookup join, exec/fused.py `lookup_join`)."""

    batch: Optional[ColumnBatch]   # UNSORTED, live rows where `live` left
    #                                them; None: the join reads no column
    keys: List[jnp.ndarray]        # sorted orderable keys (excl. null rank)
    perm: Optional[jnp.ndarray]    # int32 [capacity]: sorted position ->
    #                                row of `batch`; None with the batch
    valid_bound: jnp.ndarray       # scalar int32: rows with non-null keys
    num_rows: jnp.ndarray          # scalar int32: live rows

    @property
    def capacity(self) -> int:
        return self.keys[0].shape[0]


class BuildPositions(NamedTuple):
    """Build side of a lookup join whose ONE integer key's stamped
    range is small enough for a table of it: `table[key - lo]` is the
    build row that holds `key`, so a probe is one read where the
    sorted index takes a search of log2(slots) steps. The batch stays
    as it lies, as `BuildIndex`'s does."""

    batch: Optional[ColumnBatch]   # UNSORTED; None: the join reads no column
    table: jnp.ndarray             # int32 [hi - lo + 1]: the row holding
    #                                key lo + i; -1: none; -2 - row: that
    #                                row and at least one more
    lo: jnp.ndarray                # scalar int64: the stamped range's low end
    valid_bound: jnp.ndarray       # scalar int32: rows with non-null keys
    num_rows: jnp.ndarray          # scalar int32: live rows

    @property
    def capacity(self) -> int:
        return self.batch.capacity if self.batch is not None \
            else self.table.shape[0]


#: entries of a table of positions written by one scatter
TABLE_CHUNK = 1 << 20


def key_range(batch: ColumnBatch, key_idxs: Sequence[int]
              ) -> Optional[Tuple[int, int]]:
    """The stamped (lo, hi) of a build side's one plain integer key
    column, or None: what `build_positions` needs."""
    if len(key_idxs) != 1:
        return None
    col = batch.columns[key_idxs[0]]
    if (col.vrange is None or col.encoding is not None
            or col.data.ndim != 1
            or not jnp.issubdtype(col.data.dtype, jnp.integer)):
        return None
    return col.vrange


def build_positions(batch: ColumnBatch, key_idxs: Sequence[int],
                    live: Optional[jnp.ndarray] = None) -> BuildPositions:
    """The row-or-absent table over the key's stamped range
    (`key_range` is not None). No sort: one scatter of the row ids and
    one of a count, which marks the keys that two rows hold."""
    lo, hi = key_range(batch, key_idxs)
    size = hi - lo + 1
    cap = batch.capacity
    rows = batch.num_rows
    if live is None:
        live = batch.live_mask()
    else:
        rows = jnp.sum(live).astype(jnp.int32)
    col = batch.columns[key_idxs[0]]
    valid = live & col.validity
    # null-keyed and dead rows scatter out of range and are dropped
    at = jnp.where(valid, col.data.astype(jnp.int64) - lo,
                   size).astype(jnp.int32)
    ids = jnp.arange(cap, dtype=jnp.int32)

    def chunk(at, n):
        """Entries [0, n) of the table from positions `at` (others
        dropped): the row id, and the mark of a second holder."""
        table = jnp.full((n,), -1, jnp.int32).at[at].set(ids, mode="drop")
        held = jnp.zeros((n,), jnp.int32).at[at].add(1, mode="drop")
        return jnp.where(held > 1, -2 - table, table)

    if size <= TABLE_CHUNK:
        table = chunk(at, size)
    else:
        # a scatter into more than a few MB is one the TPU's compiler
        # SORTS the indices of (13 s of compile for 73,049 rows into
        # 4M entries, PERF.md PR 33): the table is written a chunk at
        # a time, every row offered to every chunk
        def one(k):
            rel = at - k * TABLE_CHUNK
            inside = (rel >= 0) & (rel < TABLE_CHUNK)
            return chunk(jnp.where(inside, rel, TABLE_CHUNK), TABLE_CHUNK)

        chunks = -(-size // TABLE_CHUNK)
        table = lax.map(one, jnp.arange(chunks, dtype=jnp.int32)
                        ).reshape(-1)[:size]
    return BuildPositions(batch, table, jnp.int64(lo),
                          jnp.sum(valid).astype(jnp.int32), rows)


def table_rows(entries: int) -> int:
    """Rows of 128 entries a probe by position reads a table of
    `entries` as, a row a slot; 0: more than `_POSITION_ROWS`, and it
    reads one entry a slot."""
    rows = -(-entries // _LANES)
    return rows if rows <= _POSITION_ROWS else 0


def probe_positions(build: BuildPositions, probe: ColumnBatch,
                    key_idxs: Sequence[int]
                    ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Per-probe-row (build row, matched, dup), as `probe_unique` and
    `rows_at` give them together: one read of the table, a row of 128
    entries whose lane the slot picks where the table has no more than
    `_POSITION_ROWS` of them (`table_rows`), else the entry alone."""
    live = probe.live_mask()
    vals, all_valid = _join_keys(probe, key_idxs, live)
    size = build.table.shape[0]
    at = vals[0] - build.lo
    inside = all_valid & (at >= 0) & (at < size)
    at = jnp.clip(at, 0, size - 1).astype(jnp.int32)
    if table_rows(size):
        held = _entries_by_row(_as_rows(build.table, -1), at)
    else:
        held = jnp.take(build.table, at)
    matched = inside & (held != -1)
    row = jnp.where(held < -1, -2 - held, held)
    return jnp.maximum(row, 0), matched, matched & (held < -1)


def _entries_by_row(rows: jnp.ndarray, at: jnp.ndarray) -> jnp.ndarray:
    """`rows.reshape(-1)[at]`: each slot reads its entry's row and keeps
    the lane it names (exact: the other lanes add 0), `_PICK_BLOCK`
    slots at a time, so the rows read are held a block at a time."""
    lanes = jnp.arange(_LANES, dtype=jnp.int32)

    def pick(at):
        row = jnp.take(rows, at // _LANES, axis=0)
        return jnp.sum(jnp.where(lanes == (at % _LANES)[:, None], row, 0),
                       axis=1, dtype=rows.dtype)

    n = at.shape[0]
    if n <= _PICK_BLOCK:
        return pick(at)
    blocks = -(-n // _PICK_BLOCK)
    at = jnp.pad(at, (0, blocks * _PICK_BLOCK - n))
    return lax.map(pick, at.reshape(blocks, _PICK_BLOCK)).reshape(-1)[:n]


def _join_keys(batch: ColumnBatch, key_idxs: Sequence[int],
               live: jnp.ndarray) -> Tuple[List[jnp.ndarray], jnp.ndarray]:
    """Orderable value keys + "all keys valid" mask (rank keys excluded —
    validity is handled by the bound/count-0 rules)."""
    vals: List[jnp.ndarray] = []
    all_valid = live
    for i in key_idxs:
        col = normalize_floating(batch.columns[i])
        ks = equality_keys(col, live)
        all_valid = all_valid & col.validity
        vals.extend(ks[1:])
    return vals, all_valid


_ABOVE_32 = 2 ** 31 - 1  # a plain int: nothing here may touch the backend


def _fits_32_bits(batch: ColumnBatch, key_idxs: Sequence[int]) -> bool:
    """One plain integer key column whose stamped value range (the
    narrowed upload's, exec/fused.py) leaves `_ABOVE_32` free: its
    sort needs one 32-bit operand where the general one takes a pass
    for each of two 64-bit ones, which the chip sorts, gathers and
    searches at a third of the speed."""
    stamped = key_range(batch, key_idxs)
    return (stamped is not None and -(2 ** 31) <= stamped[0]
            and stamped[1] < _ABOVE_32)


def build_index(batch: ColumnBatch, key_idxs: Sequence[int],
                live: Optional[jnp.ndarray] = None) -> BuildIndex:
    """Everything of `build_side` up to and including the sort; the
    batch stays as it is. `live` marks the batch's rows where they are
    not at its front (plan_compiler.concat_in_place): the sort sends
    the others last."""
    cap = batch.capacity
    rows = batch.num_rows
    if live is None:
        live = batch.live_mask()
    else:
        rows = jnp.sum(live).astype(jnp.int32)
    vals, all_valid = _join_keys(batch, key_idxs, live)
    if _fits_32_bits(batch, key_idxs):
        # rank and key in ONE 32-bit sort operand: a valid key lies in
        # its column's stamped range, null-keyed and dead rows take the
        # value above every one of them. The search compares these keys
        # with the probe side's 64-bit ones as they are (promotion).
        vals = [jnp.where(all_valid, vals[0].astype(jnp.int32),
                          jnp.int32(_ABOVE_32))]
        sorted_keys, perm = sorted_with_permutation(vals, cap)
    else:
        # Sort null-keyed / dead rows to the end: leading rank 0 valid,
        # 1 not; one stable pass a key array, never one sort on all
        rank = jnp.where(all_valid, 0, 1).astype(jnp.int64)
        perm = sort_permutation([rank] + vals, cap)
        sorted_keys = [jnp.take(v, perm) for v in vals]
    valid_bound = jnp.sum(all_valid).astype(jnp.int32)
    return BuildIndex(batch, sorted_keys, perm, valid_bound, rows)


def build_side(batch: ColumnBatch, key_idxs: Sequence[int],
               live: Optional[jnp.ndarray] = None) -> BuildTable:
    """The index, and the whole batch moved by its permutation."""
    idx = build_index(batch, key_idxs, live)
    return BuildTable(batch.gather(idx.perm, idx.num_rows), idx.keys,
                      idx.valid_bound)


def _tuple_cmp_at(build_keys: List[jnp.ndarray], mid: jnp.ndarray,
                  probe_keys: List[jnp.ndarray], strict: bool) -> jnp.ndarray:
    """Lexicographic: build[mid] < probe (strict) or <= probe (not strict)."""
    lt = jnp.zeros(mid.shape, dtype=bool)
    decided = jnp.zeros(mid.shape, dtype=bool)
    for bk, pk in zip(build_keys, probe_keys):
        bv = jnp.take(bk, mid)
        lt = jnp.where(~decided & (bv < pk), True, lt)
        decided = decided | (bv != pk)
    if strict:
        return lt  # undecided (equal) -> False
    return lt | ~decided  # equal counts as <=


#: keys in one node of a search's tree: the chip's lane width
_LANES = 128
#: probes searched at a time: the rows they read are held, 64 MiB of
#: 32-bit keys a level (a full-width probe of 7.9M slots would hold 4 GB)
_PROBE_BLOCK = 1024 * _LANES
#: rows of the bottom level that a block of probes whose answers lie
#: close together searches in place of the whole level: as many keys
#: as the block has probes, so a block of clustered foreign keys whose
#: parent keys each appear at least once always fits. A row read from
#: such a slice (512 KB, in fast memory) costs the chip 2.48 ns, as one
#: from 128 rows does (2.49), wherever its indices fall; from the whole
#: 63 MB level of TPC-H Q3's index 16.3 ns where a block's sorted
#: indices repeat over 27 rows, 9.3 where they are spread or shuffled
#: (PERF.md section 6, PR 36)
_WINDOW_ROWS = _PROBE_BLOCK // _LANES
#: rows of 128 entries a table of positions may have and be read a row
#: a slot: 3,670,016 uniform probes read a row and keep one lane at
#: 3.21-3.28 ns a slot from tables of 73,049 to 4,194,304 entries (292
#: KB to 16 MB), at 10.06-10.10 from 8,388,608 and 16,777,216 (32 and
#: 64 MB), where one entry costs 7.33-7.41 ns at every size (TPU v5e;
#: PERF.md section 6)
_POSITION_ROWS = 32 * 1024
#: probes that read their rows at a time: 4 MiB of rows held, 3.24-3.28
#: ns a slot (3.24 in blocks of 131,072, which the CPU backend the tests
#: run on reads four times slower); at full width the same reads write
#: 1.8 GB of rows to HBM first, 3.33-3.39 ns (PERF.md section 6)
_PICK_BLOCK = 8 * 1024


def _as_rows(keys: jnp.ndarray, fill) -> jnp.ndarray:
    """(n,) -> (ceil(n / 128), 128), the tail filled with `fill`."""
    pad = -keys.shape[0] % _LANES
    if pad:
        keys = jnp.concatenate([keys, jnp.full((pad,), fill, keys.dtype)])
    return keys.reshape(-1, _LANES)


def search_reads(slots: int) -> int:
    """Data-dependent reads one probe pays in `_count_below` over an
    array of `slots` keys: the levels of its tree, one row each."""
    reads, rows = 1, -(-max(slots, 1) // _LANES)
    while rows > _LANES:
        reads, rows = reads + 1, -(-rows // _LANES)
    return reads


def search_blocks(probes: int) -> int:
    """Blocks `_count_below` searches `probes` probes in, each free to
    take a window of the keys' bottom level; 0: they fit one block and
    are searched at once, over all of it."""
    return -(-probes // _PROBE_BLOCK) if probes > _PROBE_BLOCK else 0


def _count_below(keys: jnp.ndarray, probe: jnp.ndarray,
                 bound: Optional[jnp.ndarray], upper: bool,
                 with_equal: bool = False,
                 valid: Optional[jnp.ndarray] = None,
                 with_windowed: bool = False):
    """Per probe, how many positions `i < bound` hold `keys[i] < probe`
    (`<=` with `upper`): the lower / upper bound of each probe in a
    ONE-array key that is sorted on [0, bound) (`bound` None: all of
    it). A search whose node is a row of 128 keys: the array read as
    rows, each level above it the last keys of the rows below, the top
    level (one row at most) compared with no read. A probe reads ONE
    row a level (`search_reads`) and counts across its lanes, where a
    binary search reads one key for each of log2(n) steps: the chip
    charges a 4-byte read 7.1 ns and a 512-byte row 2.6 (PERF.md,
    PR 34). Nothing is built ahead: the levels are strided slices made
    here. `with_equal`: -> also whether the key AT a lower bound
    equals its probe, read off the row the last level fetched (the
    row that holds the bound: the level above chose the first row
    whose last key is not below the probe) and so for no read at all,
    where a gather of `keys[lo]` costs a full-width probe 27 ns a slot
    (PERF.md, PR 35).

    More probes than `_PROBE_BLOCK` are searched a block at a time
    (`search_blocks`), and each block looks at its own probes first:
    the bounds of its smallest and largest key, two probes' descent,
    enclose every other's. Where they lie within `_WINDOW_ROWS` rows
    of each other (a probe side clustered by the key it probes with:
    a fact table written in its parent's order) the block descends
    the levels of THAT window of the bottom level, a slice small
    enough for fast memory, and not the whole array's; where they do
    not, the whole array's, as a single block does. One descent, two
    sets of levels. `valid`: the probes that count for a block's
    span (None: all); the others get some position in range, and
    their callers mask them (so a block of dead slots alone, the end
    of a part that is not full, takes a window too). `with_windowed`:
    -> also how many blocks took the window, an int32 scalar."""
    n, nq = keys.shape[0], probe.shape[0]

    def asked_of(found, equal, took):
        """(positions[, equal][, blocks windowed]) as the caller asked;
        `took`: a flag a block, or None where none was searched."""
        out = (found,) + ((equal,) if with_equal else ())
        if with_windowed:
            out += (jnp.zeros((), jnp.int32) if took is None
                    else jnp.sum(took, dtype=jnp.int32),)
        return out if len(out) > 1 else out[0]

    if n == 0 or nq == 0:
        return asked_of(jnp.zeros((nq,), jnp.int32),
                        jnp.zeros((nq,), bool), None)
    integer = jnp.issubdtype(keys.dtype, jnp.integer)
    top = jnp.iinfo(keys.dtype).max if integer else jnp.inf
    bottom = jnp.iinfo(keys.dtype).min if integer else -jnp.inf
    outside = None
    if (integer and jnp.issubdtype(probe.dtype, jnp.integer)
            and probe.dtype.itemsize > keys.dtype.itemsize):
        # a wider probe is compared at the keys' width (a 64-bit
        # compare is emulated, a lane at a time); one outside that
        # width lies below or above every key
        info = jnp.iinfo(keys.dtype)
        outside = (probe < info.min, probe > info.max)
        probe = jnp.clip(probe, info.min, info.max).astype(keys.dtype)
    limit = jnp.int32(n) if bound is None else bound.astype(jnp.int32)
    lanes = jnp.arange(_LANES, dtype=jnp.int32)

    def from_row(base, row):
        # the array's row that is row `row` of a tree's bottom level
        # (None: the tree is the whole array's, and adds nothing)
        return row if base is None else base + row

    def levels_over(rows, base=None):
        """The tree over `rows`, which are the array's rows from row
        `base` on: -> (levels, bottom up; the top level's last keys,
        one row at most)."""
        levels = [rows]
        while True:
            last = levels[-1][:, -1]
            if len(levels) == 1 and bound is not None:
                # past the bound the array is not sorted: a row that
                # reaches there ends the descent
                ends = from_row(base, jnp.arange(
                    last.shape[0], dtype=jnp.int32)) * _LANES
                last = jnp.where(ends + (_LANES - 1) < limit, last, top)
            if last.shape[0] <= _LANES:
                return levels, last
            levels.append(_as_rows(last, top))

    def below(held, q):
        return held <= q if upper else held < q

    def count(hit):
        # the lanes that hit, as a product with ones: exact (128 at
        # most, of 0 and 1, summed in f32), and a matrix product where
        # a reduction across lanes costs the chip the same and the
        # CPU backend, which the tests run on, seventy times as much
        ones = jnp.ones((hit.shape[1],), jnp.bfloat16)
        return lax.dot(hit.astype(jnp.bfloat16), ones,
                       preferred_element_type=jnp.float32
                       ).astype(jnp.int32)

    def descend(q, tree, base=None):
        """Each probe's position (and `equal`) by the levels of
        `tree`, whose bottom rows are the array's from row `base`."""
        levels, last = tree
        q = q[:, None]
        node = count(below(last[None, :], q))
        equal = None
        for level in reversed(levels):
            node = jnp.minimum(node, level.shape[0] - 1)
            row = jnp.take(level, node, axis=0, mode="clip")
            hit = below(row, q)
            if level is levels[0]:
                node = from_row(base, node)
                inside = (node[:, None] * _LANES + lanes
                          < (limit if bound is not None else n))
                if bound is not None:
                    hit = hit & inside
                if with_equal:
                    equal = count((row == q) & inside) > 0
            node = node * _LANES + count(hit)
        return (node, equal) if with_equal else node

    whole = levels_over(_as_rows(keys, top))
    rows = whole[0][0].shape[0]

    def search(q):
        return descend(q, whole)

    def search_block(block):
        """One block of probes and their `valid` -> (what `search`
        gives, whether the block took the window)."""
        q, ok = block
        qmin = jnp.min(jnp.where(ok, q, top))
        qmax = jnp.max(jnp.where(ok, q, bottom))
        ends = search(jnp.stack([qmin, qmax]))
        r0, r1 = (ends[0] if with_equal else ends) // _LANES
        # (no valid probe: r1 is row 0, and any window serves)
        narrow = r1 - r0 < _WINDOW_ROWS
        if not integer:
            # a NaN among the probes says nothing of the others
            narrow = narrow & ~(jnp.isnan(qmin) | jnp.isnan(qmax))

        def windowed(q):
            start = jnp.clip(r0, 0, rows - _WINDOW_ROWS)
            win = lax.dynamic_slice(whole[0][0],
                                    (start, jnp.zeros_like(start)),
                                    (_WINDOW_ROWS, _LANES))
            return descend(q, levels_over(win, start), start)

        return (lax.cond(narrow, windowed, search, q),
                narrow.astype(jnp.int32))

    blocks, took = search_blocks(nq), None
    if not blocks:
        out = search(probe)
    else:
        pad = (0, blocks * _PROBE_BLOCK - nq)
        ok = jnp.ones((nq,), bool) if valid is None else valid
        one = search_block if rows > _WINDOW_ROWS \
            else lambda block: (search(block[0]), jnp.int32(0))
        out, took = lax.map(
            one, (jnp.pad(probe, pad).reshape(blocks, _PROBE_BLOCK),
                  jnp.pad(ok, pad).reshape(blocks, _PROBE_BLOCK)))
        out = jax.tree_util.tree_map(lambda a: a.reshape(-1)[:nq], out)
    found, equal = out if with_equal else (out, None)
    found = jnp.minimum(found, limit)
    if outside is not None:
        found = jnp.where(outside[0], 0, jnp.where(outside[1], limit, found))
        if with_equal:
            equal = equal & ~outside[0] & ~outside[1]
    return asked_of(found, equal, took)


def _binary_search(build_keys: List[jnp.ndarray],
                   probe_keys: List[jnp.ndarray], bound: jnp.ndarray,
                   build_cap: int, upper: bool,
                   valid: Optional[jnp.ndarray] = None) -> jnp.ndarray:
    """First index in [0, bound) where build[idx] >= probe (lower) or
    > probe (upper); vectorized over probe rows. A ONE-array key is
    searched a row of 128 keys a level (`_count_below`, which `valid`
    is for); a tuple of key arrays (strings packed to words, several
    columns) a key a step."""
    if len(build_keys) == 1:
        return _count_below(build_keys[0], probe_keys[0], bound, upper,
                            valid=valid)
    n = probe_keys[0].shape[0]
    lo = jnp.zeros(n, dtype=jnp.int32)
    hi = jnp.broadcast_to(bound.astype(jnp.int32), (n,))
    iters = max(1, build_cap.bit_length())

    # fori_loop, NOT an unrolled Python loop: with W key words (long
    # strings pack to max_bytes/8 words) an unrolled search emits
    # W * iters * 2 gather/compare chains and XLA compile time explodes
    # (64-byte string join: 150 s on CPU); the loop body compiles once.
    def step(_, carry):
        lo, hi = carry
        active = lo < hi
        mid = (lo + hi) >> 1
        go_right = _tuple_cmp_at(build_keys, mid, probe_keys,
                                 strict=not upper)
        new_lo = jnp.where(active & go_right, mid + 1, lo)
        new_hi = jnp.where(active & ~go_right, mid, hi)
        return new_lo, new_hi

    lo, hi = lax.fori_loop(0, iters, step, (lo, hi))
    return lo


def probe_ranges(build: BuildTable, probe: ColumnBatch,
                 key_idxs: Sequence[int]) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Per-probe-row (lo, count) of matching build rows."""
    live = probe.live_mask()
    vals, all_valid = _join_keys(probe, key_idxs, live)
    lo = _binary_search(build.keys, vals, build.valid_bound,
                        build.batch.capacity, upper=False, valid=all_valid)
    hi = _binary_search(build.keys, vals, build.valid_bound,
                        build.batch.capacity, upper=True, valid=all_valid)
    counts = jnp.where(all_valid, hi - lo, 0).astype(jnp.int32)
    return lo, counts


def _keys_equal_at(build_keys: List[jnp.ndarray], idx: jnp.ndarray,
                   probe_keys: List[jnp.ndarray]) -> jnp.ndarray:
    eq = jnp.ones(idx.shape, dtype=bool)
    for bk, pk in zip(build_keys, probe_keys):
        eq = eq & (jnp.take(bk, idx) == pk)
    return eq


def probe_matched(build: Union[BuildTable, BuildIndex],
                  probe: ColumnBatch, key_idxs: Sequence[int],
                  with_windowed: bool = False):
    """Per-probe-row (lo, matched) for a lookup join: ONE lower-bound
    search. Over a one-array key whether the build key at `lo` equals
    the probe is read off the row the search fetched last
    (`_count_below`), for no read of its own; a tuple of key arrays
    reads the keys at `lo`. `with_windowed`: -> also how many of the
    search's blocks took a window of the keys (`search_blocks`)."""
    vals, all_valid = _join_keys(probe, key_idxs, probe.live_mask())
    if len(build.keys) == 1:
        lo, equal, *took = _count_below(
            build.keys[0], vals[0], build.valid_bound, upper=False,
            with_equal=True, valid=all_valid, with_windowed=with_windowed)
        return (lo, all_valid & equal, *took)
    cap = build.keys[0].shape[0]
    lo = _binary_search(build.keys, vals, build.valid_bound, cap,
                        upper=False)
    return (lo, all_valid & _equal_at(build, lo, vals)) \
        + ((jnp.zeros((), jnp.int32),) if with_windowed else ())


def _equal_at(build, idx: jnp.ndarray, vals: List[jnp.ndarray]
              ) -> jnp.ndarray:
    cap = build.keys[0].shape[0]
    return (idx < build.valid_bound.astype(jnp.int32)) & _keys_equal_at(
        build.keys, jnp.clip(idx, 0, cap - 1), vals)


def second_match(build: Union[BuildTable, BuildIndex], probe: ColumnBatch,
                 key_idxs: Sequence[int], lo: jnp.ndarray,
                 matched: jnp.ndarray) -> jnp.ndarray:
    """Per probe row that `probe_matched` matched at `lo`, whether the
    build key at `lo + 1` equals it as well: the build keys are not
    unique for this row. One gather, which a caller that has brought
    its matches to the front of a small batch pays there and not at
    the probe's full width."""
    vals, _ = _join_keys(probe, key_idxs, probe.live_mask())
    return matched & _equal_at(build, lo + 1, vals)


def probe_unique(build: Union[BuildTable, BuildIndex], probe: ColumnBatch,
                 key_idxs: Sequence[int]
                 ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Per-probe-row (lo, matched, dup): `probe_matched` and
    `second_match` together, where both are wanted at one width."""
    lo, matched = probe_matched(build, probe, key_idxs)
    return lo, matched, second_match(build, probe, key_idxs, lo, matched)


def rows_at(build: BuildIndex, pos: jnp.ndarray
            ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """-> (the row of `build.batch` at each sorted position `pos`; a
    scalar that means nothing, which the caller returns from its
    program in a place of its own, where nothing reads it).

    The scalar is a second, plain read of the permutation. XLA:TPU
    (libtpu 0.0.34) prefetches ONE entry parameter into fast memory for
    the whole program, and takes one whose every use is a gather: left
    alone it takes the permutation, for the few rows read here, and the
    sorted keys that the search loop reads at every step stay in HBM —
    the search of Q12 then takes 21 ns a slot and step where it takes
    7.1 (PERF.md, PR 30). tests/test_chip_compile.py holds the compiler
    to it at Q12's width."""
    return jnp.take(build.perm, pos), build.perm[0] < 0


def front_row_ids(keep: jnp.ndarray, capacity: int
                  ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Row ids of the first `capacity` rows where `keep`, in order, and
    how many rows `keep` holds in all (more than `capacity`: they did
    not fit). No full-width scatter or gather: the mask's prefix sum is
    a sorted array, and rank j's row id is how many of its entries lie
    below j: `capacity` x `search_reads(n)` row reads
    (`_count_below`). Ids past the total are clamped garbage."""
    csum = jnp.cumsum(keep.astype(jnp.int32))
    j = jnp.arange(1, capacity + 1, dtype=jnp.int32)
    ids = _count_below(csum, j, None, upper=False)
    return jnp.clip(ids, 0, keep.shape[0] - 1), csum[-1]


def expand_gather_maps(lo: jnp.ndarray, counts: jnp.ndarray,
                       out_capacity: int
                       ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """(lo, counts) -> (probe_idx, build_idx, total) gather maps of static
    size out_capacity; slots >= total are clamped garbage."""
    csum = jnp.cumsum(counts.astype(jnp.int64))
    total = csum[-1].astype(jnp.int32)
    j = jnp.arange(out_capacity, dtype=jnp.int64)
    probe_idx = jnp.searchsorted(csum, j, side="right").astype(jnp.int32)
    probe_safe = jnp.clip(probe_idx, 0, counts.shape[0] - 1)
    excl = csum - counts.astype(jnp.int64)
    within = j - jnp.take(excl, probe_safe)
    build_idx = (jnp.take(lo, probe_safe).astype(jnp.int64) + within).astype(
        jnp.int32)
    build_idx = jnp.clip(build_idx, 0, None)
    return probe_safe, build_idx, total
