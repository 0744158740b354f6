"""Generator `h2o_groupby`: the h2oai db-benchmark `groupby` table `x` exactly
as generator `h2o` makes it (`lib/generator_h2o.py`: the same recipe, streams,
files, schema, reference loader and `needs` guard, imported from there), with
a comparison for answers whose columns hold strings and whose rows number in
the millions — question 10's: six keys, three of them `id%03d` / `id%010d`
strings, and about one row a group.

`generator_h2o.canonical` orders by every column as float64, which no string
converts to; here each column is first ranked by itself — a string column by
its lexicographic rank (a dictionary or categorical one through its
categories), a number by its value — and the ranks are folded into one int64
key (re-ranked by a sort whenever the next fold could overflow, and no further
once the key tells every row apart), so the rows of both sides land in one
canonical order with one stable sort. Then, row for row, the
numbers of `lib/reference.compare`, computed on arrays rather than Python
objects: `rows_off`, `cells_off` of exact columns (a null equals a null only),
`rel_err` of float columns.
"""

from __future__ import annotations

import numpy as np
import pandas as pd
import pyarrow as pa
import pyarrow.compute as pc

from lib import generator_h2o, reference

SCHEMA_FILE = generator_h2o.SCHEMA_FILE
generate = generator_h2o.generate
answers = generator_h2o.answers
worst = reference.worst

_FOLD_LIMIT = 1 << 62


def _ranks(col: pd.Series) -> tuple[np.ndarray, int]:
    """Dense ranks of one column in the order the canonical sort uses (nulls
    first), and how many distinct ranks there can be."""
    if isinstance(col.dtype, pd.CategoricalDtype):
        cats = np.asarray(col.cat.categories, dtype=object)
        lex = np.empty(len(cats), dtype=np.int64)
        lex[np.argsort(cats, kind="stable")] = np.arange(len(cats))
        codes = col.cat.codes.to_numpy()
        return np.where(codes < 0, 0, lex[codes] + 1), len(cats) + 1
    if (pd.api.types.is_integer_dtype(col.dtype)
            and not pd.api.types.is_extension_array_dtype(col.dtype)):
        values = col.to_numpy().astype(np.int64)
        low, high = (int(values.min()), int(values.max())) if len(values) else (0, 0)
        if high - low < 4 * len(values) + 16:  # a dense domain: the value is its rank
            return values - low, high - low + 1
    if pd.api.types.is_numeric_dtype(col.dtype) and not pd.api.types.is_bool_dtype(col.dtype):
        values = col.to_numpy(dtype=np.float64, na_value=np.nan)
        uniq, inverse = np.unique(values, return_inverse=True)  # NaN sorts last
        return inverse.astype(np.int64), len(uniq)
    codes, uniq = pd.factorize(col, sort=True)  # nulls: -1
    return codes.astype(np.int64) + 1, len(uniq) + 1


def canonical(df: pd.DataFrame) -> pd.DataFrame:
    """The rows in one order that depends on their values alone: by every
    column in turn, ascending."""
    if not len(df) or not df.shape[1]:
        return df
    key, span = np.zeros(len(df), dtype=np.int64), 1
    for i in range(df.shape[1]):
        # a column holds at most 4 * rows + 17 ranks (`_ranks`); where that
        # many may not fit beside the key, re-rank the key by one sort, and
        # stop where it already tells every row apart: the columns after it
        # cannot change the order (question 10: its six keys)
        if span * (4 * len(df) + 17) >= _FOLD_LIMIT:
            order = np.argsort(key)  # tied rows are equal so far: any order of them
            ordered = key[order]
            new = np.ones(len(key), dtype=bool)
            new[1:] = ordered[1:] != ordered[:-1]
            if new.all():
                return df.iloc[order].reset_index(drop=True)
            key = np.empty(len(key), dtype=np.int64)
            key[order] = np.cumsum(new) - 1
            span = int(key.max()) + 1
        r, n = _ranks(df.iloc[:, i])
        key, span = key * n + r, span * n
    return df.iloc[np.argsort(key, kind="stable")].reset_index(drop=True)


def _exact_off(a: pd.Series, b: pd.Series) -> int:
    """Cells of an exact column that differ; a null equals a null only."""
    if (pd.api.types.is_integer_dtype(a.dtype) and pd.api.types.is_integer_dtype(b.dtype)
            and not pd.api.types.is_extension_array_dtype(a.dtype)
            and not pd.api.types.is_extension_array_dtype(b.dtype)):
        return int((a.to_numpy() != b.to_numpy()).sum())
    x, y = pa.array(a, from_pandas=True), pa.array(b, from_pandas=True)
    if pa.types.is_dictionary(x.type):
        x = x.cast(x.type.value_type)
    if pa.types.is_dictionary(y.type):
        y = y.cast(y.type.value_type)
    if x.type != y.type:
        return reference.compare(a.to_frame(), b.to_frame())["cells_off"]
    same = pc.fill_null(pc.equal(x, y), False)
    both_null = pc.and_(pc.is_null(x), pc.is_null(y))
    return len(x) - pc.sum(pc.or_(same, both_null).cast(pa.int64())).as_py()


_WANT: dict = {}  # the reference's answer in canonical order, by the frame's id
_LAST: list = []  # [got, want, numbers] of the last comparison made


def compare(got: pd.DataFrame, want: pd.DataFrame) -> dict[str, float]:
    if got.shape[1] != want.shape[1] or len(got) != len(want):
        return reference.compare(got, want)
    # an answer equal, value for value and in the same order, to the one
    # compared last with the same reference reads what that one read (a
    # run's answers repeat: the first round's and the window's)
    if _LAST and _LAST[1] is want and got.equals(_LAST[0]):
        return dict(_LAST[2])
    given = got.copy()  # held apart from the caller's frame, which may change
    # a run compares every answer with the same reference frame: order it once
    held = _WANT.get(id(want))
    if held is None or held[0] is not want:
        _WANT.clear()
        held = _WANT[id(want)] = (want, canonical(want))
    numbers = _numbers(canonical(got), held[1])
    _LAST[:] = [given, want, numbers]
    return dict(numbers)


def _numbers(got: pd.DataFrame, want: pd.DataFrame) -> dict[str, float]:
    """`rows_off`, `cells_off` and `rel_err` of two frames in one order."""
    cells_off, rel_err = 0, 0.0
    for i, col in enumerate(want.columns):
        a, b = got.iloc[:, i], want[col]
        if pd.api.types.is_float_dtype(b):
            a = a.to_numpy(dtype=np.float64, na_value=np.nan)
            b = b.to_numpy(dtype=np.float64, na_value=np.nan)
            gap = np.abs(a - b) / np.where(b == 0, 1.0, np.abs(b))
            gap = np.where(np.isnan(a) != np.isnan(b), 1.0, np.nan_to_num(gap))
            rel_err = max(rel_err, float(gap.max(initial=0.0)))
        else:
            cells_off += _exact_off(a, b)
    return {"rows_off": 0, "cells_off": cells_off, "rel_err": rel_err}
