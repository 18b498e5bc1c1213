"""``correct`` has a control that fails it, and faults that fail it.

The control: the plain reference in float32 in the program's place
(``control.py``; read at each cell's scale on three seeds for
PERF.md, here at SF0.01).  The faults a cell of this benchmark can have, planted under
``run.py`` in the program itself: an answer altered where it is
produced, and half of the rows left out (sums halve, a mean is taken
over the rest).  A state left unchanged and an exchange between chips
left out are no faults of these cells: they keep no state between
queries and run on one chip."""

import pyarrow as pa
import pytest

from conftest import cells


@pytest.mark.parametrize("cell", cells())
def test_float32_control_is_not_correct(cell):
    import control
    for seed in (1, 2147483659, 77):
        r = control.read_control(cell, seed, sf=0.01)
        assert r["correct"] is False, r
        assert r["checks"]["max_rel_err"]["value"] > 3e-9, r


def _bump(table: pa.Table) -> pa.Table:
    """The last row's first double up by 1e-6 of itself, or where the
    answer has no double its first count up by one."""
    for i, f in enumerate(table.schema):
        if pa.types.is_floating(f.type) or pa.types.is_integer(f.type):
            v = table.column(i).to_pylist()
            if pa.types.is_floating(f.type):
                v[-1] = v[-1] * (1 + 1e-6)
            else:
                v[-1] = v[-1] + 1
            return table.set_column(i, f, pa.array(v, type=f.type))
    raise AssertionError("no number in the answer")


@pytest.mark.parametrize("cell", cells())
def test_an_altered_answer_is_not_correct(cell, rehearse, monkeypatch):
    from spark_rapids_tpu.sql.dataframe import DataFrame
    real = DataFrame.toArrow
    state = {"n": 0}

    def altered(self, *a, **kw):
        out = real(self, *a, **kw)
        state["n"] += 1
        # one answer in seven, so that most of the window is sound
        return _bump(out) if state["n"] % 7 == 0 else out

    monkeypatch.setattr(DataFrame, "toArrow", altered)
    code, result, err = rehearse(cell)
    assert code == 0 and result["correct"] is False
    c = result["checks"]
    assert (c["max_rel_err"]["value"] > c["max_rel_err"]["limit"]
            or c["exact_mismatches"]["value"] > 0)
    assert err.strip().splitlines()[-1] == "correct: False"


@pytest.mark.parametrize("cell", cells())
def test_half_of_the_rows_left_out_is_not_correct(cell, rehearse,
                                                  monkeypatch):
    from spark_rapids_tpu.sql.session import TpuSession
    real = TpuSession.createDataFrame

    def half(self, data, schema=None):
        if isinstance(data, pa.Table) and data.num_rows > 10_000:
            data = data.slice(0, data.num_rows // 2)
        return real(self, data, schema)

    monkeypatch.setattr(TpuSession, "createDataFrame", half)
    code, result, _ = rehearse(cell)
    assert code == 0 and result["correct"] is False


def test_a_failed_request_is_not_correct(rehearse, monkeypatch):
    from spark_rapids_tpu.sql.dataframe import DataFrame
    real = DataFrame.toArrow
    state = {"n": 0}

    def failing(self, *a, **kw):
        state["n"] += 1
        if state["n"] == 12:
            raise RuntimeError("planted")
        return real(self, *a, **kw)

    monkeypatch.setattr(DataFrame, "toArrow", failing)
    code, result, _ = rehearse("session.q6")
    assert code == 0 and result["failed"] == 1
    assert result["correct"] is False
    assert result["checks"]["unanswered"]["value"] == 1
