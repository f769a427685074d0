"""The comparison that decides `correct`: an answer of the timed path
against the query's plain reference on the same files.

A query's ANSWER says what kind of column each is: `keys` identify a
row, `exact` columns (counts) must be equal, `approx` columns (sums
and averages of doubles) are compared by relative error, `order` and
`limit` are the query's. The reference is the WHOLE ordered answer,
not cut to the limit, so that a row of the answer that the reference
ranks just beyond the limit can be judged a tie and not an intruder.

Two numbers come out, each with a limit of its own
(`limits/<cell>.json`):

- `rows_wrong`, limit 0: rows missing, unknown or twice in the answer,
  rows whose exact columns differ, neighbours out of the stated order,
  and rows past the limit's cut. Where the order or the cut depends on
  an `approx` column, two rows whose values lie within the limit on
  `sum_rel_err` of each other may swap: the engine's order by its own
  sums is then as right as the reference's.
- `sum_rel_err`: the largest |got - want| / |want| over every
  `approx` value of every row of the answer.
"""

import math


def _rows(table, names):
    cols = [table.column(n).to_pylist() for n in names]
    return list(zip(*cols)) if cols else [()] * table.num_rows


def _close(a, b, tol: float) -> bool:
    return abs(a - b) <= tol * max(abs(a), abs(b))


def compare_answer(got, want, answer: dict, tie_tol: float) -> dict:
    """-> {"rows_wrong": int, "sum_rel_err": float}. `got` is the
    engine's table, `want` the reference's whole ordered answer."""
    keys, exact, approx = answer["keys"], answer["exact"], answer["approx"]
    order, limit = answer["order"], answer["limit"]
    need = set(keys + exact + approx)
    if not need <= set(got.column_names):
        return {"rows_wrong": max(1, want.num_rows), "sum_rel_err": math.inf}
    want_by_key = {k: i for i, k in enumerate(_rows(want, keys))}
    want_cols = {n: want.column(n).to_pylist()
                 for n in set(exact + approx + [c for c, _ in order])}
    got_cols = {n: got.column(n).to_pylist() for n in exact + approx}
    expect_rows = want.num_rows if limit is None else min(limit, want.num_rows)
    wrong = abs(got.num_rows - expect_rows)
    worst = 0.0
    seen, ranks = set(), []
    for j, key in enumerate(_rows(got, keys)):
        i = want_by_key.get(key)
        if i is None or key in seen:
            wrong += 1
            continue
        seen.add(key)
        ranks.append((j, i))
        if any(got_cols[n][j] != want_cols[n][i] for n in exact):
            wrong += 1
        for n in approx:
            g, w = got_cols[n][j], want_cols[n][i]
            if g is None or w is None or math.isnan(g):
                worst = math.inf
            elif g != w:
                worst = max(worst, abs(g - w) / abs(w) if w else math.inf)

    def tied(i1: int, i2: int) -> bool:
        """May reference rows i1 and i2 stand in either order?"""
        for col, _ in order:
            if col in approx:
                return _close(want_cols[col][i1], want_cols[col][i2], tie_tol)
            if want_cols[col][i1] != want_cols[col][i2]:
                return False
        return True

    if order:
        for (_, a), (_, b) in zip(ranks, ranks[1:]):
            if a > b and not tied(a, b):
                wrong += 1
        if limit is not None and want.num_rows > limit:
            # a row from beyond the cut is right only if it ties with
            # the last row the reference keeps
            for _, i in ranks:
                if i >= limit and not tied(limit - 1, i):
                    wrong += 1
    return {"rows_wrong": wrong, "sum_rel_err": worst}


def compare_all(answers: list, references: dict, specs: dict,
                tie_tol: float) -> dict:
    """Every answer of the window: `answers` is [(query name, table)].
    Equal tables are compared once. -> the two numbers, worst of all,
    with `answers` and `distinct` counted."""
    out = {"rows_wrong": 0, "sum_rel_err": 0.0}
    judged = {}
    for name, table in answers:
        for seen, res in judged.get(name, []):
            if table.equals(seen):
                break
        else:
            res = compare_answer(table, references[name], specs[name],
                                 tie_tol)
            judged.setdefault(name, []).append((table, res))
        out["rows_wrong"] += res["rows_wrong"]
        out["sum_rel_err"] = max(out["sum_rel_err"], res["sum_rel_err"])
    out["answers"] = len(answers)
    out["distinct"] = sum(len(v) for v in judged.values())
    return out
