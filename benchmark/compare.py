"""The comparison that decides ``correct``: an answer the window
returned against the plain reference's table for the same binding.

The guarantees a configuration states (``configs/*.json``): keys,
counts, row order and null positions are exact; a ``double`` agrees to
``double_rtol`` with +0.0 and -0.0 equal.  Two numbers come out of an
answer: how many of its exact things differ, and the widest relative
gap of its doubles."""

import numpy as np
import pyarrow as pa


def compare_tables(got: pa.Table, want: pa.Table) -> dict:
    """``exact_mismatches``: 1 for a schema or row-count difference
    (nothing further is compared then), else the number of columns whose
    exact content (values of a non-double column; null and NaN
    positions of a double column) differs.  ``max_rel_err``: the widest
    |got - want| / |want| over the doubles (absolute against an exact
    zero)."""
    if got.schema.names != want.schema.names:
        return {"exact_mismatches": 1, "max_rel_err": 0.0,
                "what": f"columns {got.schema.names} != {want.schema.names}"}
    if got.schema.types != want.schema.types:
        return {"exact_mismatches": 1, "max_rel_err": 0.0,
                "what": f"types {got.schema.types} != {want.schema.types}"}
    if got.num_rows != want.num_rows:
        return {"exact_mismatches": 1, "max_rel_err": 0.0,
                "what": f"rows {got.num_rows} != {want.num_rows}"}
    bad, worst, what = 0, 0.0, []
    for name in want.column_names:
        g, w = got.column(name), want.column(name)
        if not pa.types.is_floating(w.type):
            if not g.equals(w):
                bad += 1
                what.append(f"{name}: exact column differs")
            continue
        gv = g.to_numpy(zero_copy_only=False).astype(np.float64)
        wv = w.to_numpy(zero_copy_only=False).astype(np.float64)
        if (g.null_count or w.null_count) and (
                g.is_valid().to_pylist() != w.is_valid().to_pylist()):
            bad += 1
            what.append(f"{name}: null positions differ")
            continue
        live = ~np.isnan(wv)
        if not np.array_equal(~np.isnan(gv), live):
            bad += 1
            what.append(f"{name}: NaN positions differ")
            continue
        mag = np.abs(wv[live])
        rel = np.abs(gv[live] - wv[live]) / np.where(mag > 0, mag, 1.0)
        if rel.size:
            worst = max(worst, float(rel.max()))
    return {"exact_mismatches": bad, "max_rel_err": worst,
            "what": "; ".join(what)}


def verdict(checks: dict) -> bool:
    """``checks`` maps a name to ``{"value", "limit"}`` and, for the one
    number that has to be reached and not kept under, ``"at_least"``."""
    for c in checks.values():
        if c.get("at_least"):
            if not c["value"] >= c["limit"]:
                return False
        elif not c["value"] <= c["limit"]:
            return False
    return True
