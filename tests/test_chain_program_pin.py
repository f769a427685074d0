"""Chains without a join trace to the programs they had: the lowered
text of Q1's and Q6's per-partition chain programs (the benchmark's
two accepted cells, built as `benchmark/run.py` builds them, from the
cells' own files, over a small seeded `lineitem`) is pinned by digest. The digests were taken
at the parent of the PR that taught the lookup join to search its
filter's survivors (which touches `chain_traced`, the flags vector and
the program key); a PR that means to change these programs re-pins
them and says so."""

import hashlib

import jax
import pytest

from benchmark import run as bench
from spark_rapids_tpu.api.session import TpuSparkSession
from spark_rapids_tpu.runtime import jit_cache

#: cell -> (its chain program, the digest of that program's text)
PINNED = {
    "tpch_q1_resident": ("fused_chain_831fc292", "235b313e7048f65b"),
    "tpch_q6_scan_uncached": ("fused_chain_02fea743", "e2dde199874049cb"),
}
ROWS = 40_000


def chain_programs(monkeypatch, session, df):
    """{program name: lowered text} of the chain programs `df` runs."""
    real, seen = jit_cache.cached_jit, {}

    def recording(key, build, **kw):
        jitted = real(key, build, **kw)
        if key[:2] != ("fused", "chain"):
            return jitted

        def call(*args):
            fn = build()
            seen[fn.__name__] = jax.jit(fn).lower(*args).as_text()
            return jitted(*args)

        return call

    monkeypatch.setattr(jit_cache, "cached_jit", recording)
    df.collect_arrow()
    assert session.last_execution["engine"] == "fused"
    return seen


@pytest.mark.parametrize("name", list(PINNED))
def test_chain_program_is_byte_equal_to_the_pinned_one(
        monkeypatch, tmp_path, name):
    cell = bench.load_cell(name)
    # one scan task a file, as at the cells' size, where no two files
    # fit one task: small files would coalesce into one partition and
    # the plan would hold a `complete` aggregate outside the chain
    session = TpuSparkSession(dict(
        cell["config"]["session_conf"],
        **{"spark.rapids.sql.format.parquet.reader.type": "PERFILE"}))
    try:
        _check(monkeypatch, tmp_path, name, cell, session)
    finally:
        session.stop()


def _check(monkeypatch, tmp_path, name, cell, session):
    generator = bench.load_module("datagen", cell["config"]["generator"])
    dirs = generator.generate(cell["config"], 7, str(tmp_path), rows=ROWS)
    tables = bench.open_tables(session, cell, dirs)
    (query,) = cell["queries"].values()
    programs = chain_programs(monkeypatch, session,
                              query.build(session, tables))
    program, digest = PINNED[name]
    assert list(programs) == [program]
    got = hashlib.sha256(programs[program].encode()).hexdigest()[:16]
    assert got == digest, got
