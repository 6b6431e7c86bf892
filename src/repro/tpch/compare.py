"""Engine-vs-oracle result comparison for TPC-H queries: the one
comparator the tests and ``chip_smoke.py`` share."""

from __future__ import annotations

import numpy as np


def assert_results_match(engine: dict, oracle: dict, qnum: int,
                         float_cols_rtol: float = 2e-3):
    """Raise ``AssertionError`` unless ``engine`` holds the same rows as
    ``oracle`` (order-insensitive) on their common columns: integer,
    bytes and string columns exactly, float columns to ``float_cols_rtol``.
    """
    common = [c for c in oracle.keys() if c in engine]
    assert common, f"q{qnum}: no common columns {list(engine)} vs {list(oracle)}"
    n_e = len(next(iter(engine.values())))
    n_o = len(next(iter(oracle.values())))
    assert n_e == n_o, f"q{qnum}: row count {n_e} != oracle {n_o}"
    # order-insensitive structural match on non-float columns, then
    # float columns compared after canonical sort
    int_cols = [c for c in common if np.asarray(oracle[c]).dtype.kind in "iub"
                or isinstance(oracle[c][0] if n_o else b"", bytes)]
    flt_cols = [c for c in common if c not in int_cols]
    key_cols = int_cols if int_cols else common

    def sort_rows(res):
        arrays = []
        for c in key_cols + flt_cols:
            a = res[c]
            if isinstance(a, np.ndarray) and a.ndim > 1 and a.dtype == np.uint8:
                a = np.array([row.tobytes() for row in a])
            elif n_o and isinstance(a[0], bytes):
                a = np.asarray(a)
            else:
                a = np.asarray(a, dtype=np.float64)
                a = np.round(a, 2)
            arrays.append(a)
        order = np.lexsort(tuple(reversed(arrays)))
        return order

    eo, oo = sort_rows(engine), sort_rows(oracle)
    for c in int_cols:
        ea, oa = engine[c], oracle[c]
        if isinstance(ea, np.ndarray) and ea.ndim > 1 and ea.dtype == np.uint8:
            ea = np.array([r.tobytes() for r in ea])
        if isinstance(oa, np.ndarray) and oa.ndim > 1 and oa.dtype == np.uint8:
            # reference side may be another engine result (differential
            # engine-vs-engine checks): same bytes-row canonicalization
            oa = np.array([r.tobytes() for r in oa])
        if n_o and isinstance(oa[0], bytes):
            oa = np.asarray(oa)
            ea = np.asarray(ea)
        np.testing.assert_array_equal(np.asarray(ea)[eo], np.asarray(oa)[oo],
                                      err_msg=f"q{qnum} column {c}")
    for c in flt_cols:
        ea = np.asarray(engine[c], dtype=np.float64)[eo]
        oa = np.asarray(oracle[c], dtype=np.float64)[oo]
        np.testing.assert_allclose(ea, oa, rtol=float_cols_rtol, atol=1e-2,
                                   err_msg=f"q{qnum} column {c}")
