"""DataFrame API over logical plans (pyspark.sql.DataFrame surface).

Eager analysis (names resolve at call time, like pyspark), lazy execution.
``_execute`` runs the full pipeline: physical planning → TPU overrides
rewrite (plan/overrides.py) → partition pump → arrow collect.
"""

from __future__ import annotations

from typing import List, Optional, Sequence, Tuple, Union

import pyarrow as pa

from spark_rapids_tpu.columnar import dtypes as T
from spark_rapids_tpu.columnar import host as H
from spark_rapids_tpu.plan import analysis as AN
from spark_rapids_tpu.plan import logical as L
from spark_rapids_tpu.plan.overrides import apply_overrides
from spark_rapids_tpu.plan.planner import plan_physical
from spark_rapids_tpu.sql.column import Column, UExpr, col as _col


def open_books(conf, qid: int):
    """The tracer and the flight recorder of query ``qid``, installed
    on the calling thread (runtime/inflight.py): ``(tracer, recorder)``,
    each None where it is switched off or the thread already has an
    owner (a nested execution rides it).  ``DataFrame.toArrow`` opens
    them for a direct execution; ``QueryServer`` opens them on the
    worker before the run slot is granted and hands them to
    ``toArrow``.

    The attribution plane rides the tracer: when attribution is on (the
    default) the tracer runs even with trace.enabled off, but
    _record_query only emits the rollup the user asked for — the spans
    feed the ledger + flight recorder."""
    from spark_rapids_tpu import conf as C
    from spark_rapids_tpu.runtime import attribution as attr_mod
    from spark_rapids_tpu.runtime import trace
    attr_on = bool(conf.get(C.ATTRIBUTION_ENABLED))
    tracer = None
    if conf.get(C.TRACE_ENABLED) or attr_on:
        tracer = trace.start_query(qid)
    arec = None
    if attr_on:
        arec = attr_mod.start_query(qid)
        if tracer is not None and arec is not None:
            tracer.recorder = arec
    return tracer, arec


class Row(tuple):
    """Lightweight pyspark.Row analog: tuple + field access."""

    def __new__(cls, values, fields):
        r = super().__new__(cls, values)
        r.__dict__ = {}
        r.__dict__["_fieldnames"] = fields
        return r

    def __getattr__(self, item):
        names = self.__dict__.get("_fieldnames", ())
        if item in names:
            return self[names.index(item)]
        raise AttributeError(item)

    def __getitem__(self, item):
        if isinstance(item, str):
            return self[self.__dict__["_fieldnames"].index(item)]
        return super().__getitem__(item)

    def asDict(self):
        return dict(zip(self.__dict__["_fieldnames"], self))

    def __repr__(self):
        names = self.__dict__.get("_fieldnames", ())
        inner = ", ".join(f"{n}={v!r}" for n, v in zip(names, self))
        return f"Row({inner})"


def _to_column(c: Union[str, Column]) -> Column:
    return _col(c) if isinstance(c, str) else c


class StructSpec:
    """One logical STRUCT column, physically stored flattened
    (struct-of-arrays — the TPU-native layout; arrow stores structs the
    same way).  ``fields``: [(field name, physical column name)];
    ``null_col``: physical bool column marking null structs (absent when
    the struct column has no nulls).
    [REF: complexTypeCreator.scala / cuDF struct columns — here structs
    are a FRONTEND view; every kernel sees plain columns]"""

    __slots__ = ("fields", "null_col")

    def __init__(self, fields, null_col=None):
        self.fields = list(fields)
        self.null_col = null_col

    @property
    def phys_cols(self):
        out = [p for _, p in self.fields]
        if self.null_col:
            out.append(self.null_col)
        return out

    def renamed(self, new_name: str) -> "StructSpec":
        return StructSpec(
            [(f, f"{new_name}.{f}") for f, _ in self.fields],
            f"{new_name}#null" if self.null_col else None)


class DataFrame:
    def __init__(self, session, plan: L.LogicalPlan, structs=None):
        self.session = session
        self._plan = plan
        # logical struct columns over the flattened physical schema
        self._structs: dict = dict(structs or {})

    def _derive(self, plan: L.LogicalPlan,
                structs="inherit") -> "DataFrame":
        """New frame over ``plan``; struct specs propagate when every
        physical column survived (schema-preserving ops), else pass the
        recomputed specs explicitly."""
        if structs == "inherit":
            names = set(plan.schema.field_names())
            structs = {k: v for k, v in self._structs.items()
                       if all(p in names for p in v.phys_cols)}
        return DataFrame(self.session, plan, structs)

    # every transformation body below constructs through this (a plain
    # textual stand-in for `DataFrame(self.session, ...)` that keeps
    # struct specs flowing)
    _derive_ctor = _derive

    @staticmethod
    def _adopt_structs(out: "DataFrame", other: "DataFrame"
                       ) -> "DataFrame":
        """Merge the right join side's struct specs into the result
        (kept only when every physical column survived)."""
        names = set(out.schema.field_names())
        for k, v in other._structs.items():
            if k not in out._structs and all(p in names
                                             for p in v.phys_cols):
                out._structs[k] = v
        return out

    # -- metadata -----------------------------------------------------------
    @property
    def schema(self) -> T.StructType:
        return self._plan.schema

    @property
    def columns(self) -> List[str]:
        return self.schema.field_names()

    def _struct_name_of(self, c) -> Optional[str]:
        """The struct-column name ``c`` denotes (bare string or a plain
        ``col('s')`` reference), else None."""
        if isinstance(c, str):
            return c if c in self._structs else None
        if isinstance(c, Column) and c._u.op == "attr" \
                and c._u.payload in self._structs:
            return c._u.payload
        return None

    def _expand_struct_names(self, cols):
        """Replace bare struct-column names/refs with their physical
        columns (null flag included — null structs group/sort as one
        value)."""
        out = []
        for c in cols:
            sname = self._struct_name_of(c)
            if sname is not None:
                out.extend(self._structs[sname].phys_cols)
            else:
                out.append(c)
        return out

    # -- transformations ----------------------------------------------------
    def select(self, *cols) -> "DataFrame":
        if any(self._generate_u(c) is not None for c in cols
               if not (isinstance(c, str) and c == "*")):
            return self._select_with_generate(cols)
        if any(self._pyudf_u(c) is not None for c in cols
               if not (isinstance(c, str) and c == "*")):
            if any(self._window_u(c) is not None for c in cols
                   if not (isinstance(c, str) and c == "*")):
                raise AN.AnalysisException(
                    "cannot mix python UDFs and window functions in one "
                    "select — materialize one of them first "
                    "(e.g. withColumn)")
            return self._select_with_pyudfs(cols)
        if any(self._window_u(c) is not None for c in cols
               if not (isinstance(c, str) and c == "*")):
            return self._select_with_windows(cols)
        from spark_rapids_tpu.ops.expressions import BoundReference
        exprs = []
        fields = []
        new_structs = {}

        def add_ref(name):
            i = self.schema.field_index(name)
            f = self.schema.fields[i]
            exprs.append(BoundReference(i, f.dtype, f.nullable))
            fields.append(f)

        for c in cols:
            if isinstance(c, str) and c == "*":
                for i, f in enumerate(self.schema.fields):
                    exprs.append(BoundReference(i, f.dtype, f.nullable))
                    fields.append(f)
                continue
            sname = self._struct_name_of(c)
            if sname is not None:
                # selecting a struct column = selecting its flattened
                # physical columns; the spec rides along
                spec = self._structs[sname]
                for p in spec.phys_cols:
                    add_ref(p)
                new_structs[sname] = spec
                continue
            u = _to_column(c)._u
            if (u.op == "alias" and u.children[0].op == "attr"
                    and u.children[0].payload in self._structs):
                # struct rename: re-emit the physical columns under the
                # new name's flattened layout
                spec = self._structs[u.children[0].payload]
                new = spec.renamed(u.payload)
                for (_, old_p), (_, new_p) in zip(spec.fields,
                                                  new.fields):
                    i = self.schema.field_index(old_p)
                    f = self.schema.fields[i]
                    exprs.append(BoundReference(i, f.dtype, f.nullable))
                    fields.append(T.StructField(new_p, f.dtype,
                                                f.nullable))
                if spec.null_col:
                    i = self.schema.field_index(spec.null_col)
                    f = self.schema.fields[i]
                    exprs.append(BoundReference(i, f.dtype, f.nullable))
                    fields.append(T.StructField(new.null_col, f.dtype,
                                                f.nullable))
                new_structs[u.payload] = new
                continue
            mk = u.children[0] if u.op == "alias" else u
            if mk.op == "make_struct":
                # F.struct(...): emit one physical column per field +
                # record the spec [REF: complexTypeCreator CreateStruct]
                sname = (u.payload if u.op == "alias"
                         else f"struct_{len(new_structs)}")
                sfields = []
                for fname, fu in zip(mk.payload, mk.children):
                    e = AN.resolve(fu, self.schema)
                    pname = f"{sname}.{fname}"
                    exprs.append(e)
                    fields.append(T.StructField(pname, e.dtype))
                    sfields.append((fname, pname))
                new_structs[sname] = StructSpec(sfields, None)
                continue
            u2 = self._rewrite_struct_ref(u)
            e = AN.resolve(u2, self.schema)
            name = self._output_name(u, e)
            exprs.append(e)
            fields.append(T.StructField(name, e.dtype))
        schema = T.StructType(tuple(fields))
        out = self._derive_ctor(L.Project(self._plan, exprs, schema))
        out._structs.update(new_structs)
        return out

    def _rewrite_struct_ref(self, u: UExpr) -> UExpr:
        """col('s') for a logical struct has no physical column; rewrite
        getField chains to the flattened name ('s'.getField('a') →
        attr 's.a')."""
        if u.op == "getfield":
            child = self._rewrite_struct_ref(u.children[0])
            if child.op == "attr":
                return UExpr("attr", f"{child.payload}.{u.payload}")
            raise AN.AnalysisException(
                "getField is only supported on (nested) column "
                "references")
        if not u.children:
            return u
        kids = tuple(self._rewrite_struct_ref(c) for c in u.children)
        if all(a is b for a, b in zip(kids, u.children)):
            return u
        return UExpr(u.op, u.payload, kids)

    @staticmethod
    def _generate_u(c) -> Optional[UExpr]:
        """The explode/posexplode UExpr under an optional alias."""
        if isinstance(c, str):
            return None
        u = _to_column(c)._u
        core = u.children[0] if u.op == "alias" else u
        return core if core.op == "generate" else None

    def _select_with_generate(self, cols) -> "DataFrame":
        """Spark's ExtractGenerator analog: one Generate node appends
        pos/element columns to the child, then a Project picks the
        requested output."""
        from spark_rapids_tpu.ops.expressions import BoundReference
        gens = [c for c in cols
                if not (isinstance(c, str) and c == "*")
                and self._generate_u(c) is not None]
        if len(gens) > 1:
            raise AN.AnalysisException(
                "only one generator (explode/posexplode) is allowed per "
                "select")
        base_schema = self.schema
        gu = self._generate_u(gens[0])
        with_pos, outer = gu.payload
        gen_expr = AN.resolve(gu.children[0], base_schema)
        if not isinstance(gen_expr.dtype, T.ArrayType):
            raise AN.AnalysisException(
                f"explode needs an array column, got "
                f"{gen_expr.dtype.simple_name}")
        alias_u = _to_column(gens[0])._u
        elem_name = (alias_u.payload if alias_u.op == "alias" else "col")
        elem_dt = gen_expr.dtype.element_type
        nc = len(base_schema)
        ext_fields = list(base_schema.fields)
        if with_pos:
            ext_fields.append(T.StructField("pos", T.IntegerT, outer))
        ext_fields.append(T.StructField(elem_name, elem_dt, True))
        ext_schema = T.StructType(tuple(ext_fields))
        plan = L.Generate(self._plan, gen_expr, with_pos, outer,
                          ext_schema)
        exprs, fields = [], []
        for c in cols:
            if isinstance(c, str) and c == "*":
                for i, f in enumerate(base_schema.fields):
                    exprs.append(BoundReference(i, f.dtype, f.nullable))
                    fields.append(f)
                continue
            if self._generate_u(c) is not None:
                if with_pos:
                    exprs.append(BoundReference(nc, T.IntegerT, outer))
                    fields.append(T.StructField("pos", T.IntegerT, outer))
                idx = nc + (1 if with_pos else 0)
                exprs.append(BoundReference(idx, elem_dt, True))
                fields.append(T.StructField(elem_name, elem_dt, True))
                continue
            u = _to_column(c)._u
            e = AN.resolve(u, ext_schema)
            exprs.append(e)
            fields.append(T.StructField(self._output_name(u, e), e.dtype))
        return self._derive_ctor( L.Project(
            plan, exprs, T.StructType(tuple(fields))))

    @staticmethod
    def _pyudf_u(c) -> Optional[UExpr]:
        if isinstance(c, str):
            return None
        u = _to_column(c)._u
        core = u.children[0] if u.op == "alias" else u
        return core if core.op == "pyudf" else None

    def _select_with_pyudfs(self, cols) -> "DataFrame":
        """Spark's ExtractPythonUDFs analog: one PythonEval node appends
        every UDF result column, then a Project picks the output.

        With ``spark.rapids.sql.udfCompiler.enabled`` the AST compiler
        first tries to lower each UDF onto the expression tree
        [REF: udf-compiler/ :: CatalystExpressionBuilder]; compiled UDFs
        become plain device expressions and skip the bridge entirely."""
        from spark_rapids_tpu import conf as C
        from spark_rapids_tpu.exec.python_udf import PyUDFSpec
        from spark_rapids_tpu.ops.expressions import BoundReference
        compile_enabled = bool(self.session.rapids_conf().get(
            C.UDF_COMPILER_ENABLED))
        base_schema = self.schema
        nc = len(base_schema)
        udfs = []
        out_specs = []
        for c in cols:
            if isinstance(c, str) and c == "*":
                out_specs.append(("plain", c))
                continue
            uu = self._pyudf_u(c)
            if uu is None:
                out_specs.append(("plain", c))
                continue
            fn, dt, vectorized, fname = uu.payload
            args = [AN.resolve(a, base_schema) for a in uu.children]
            u = _to_column(c)._u
            alias = u.payload if u.op == "alias" else None
            name = alias or f"{fname}({', '.join(map(str, args))})"
            if compile_enabled:
                from spark_rapids_tpu.sql.udf_compiler import (
                    UdfCompileError, compile_udf)
                try:
                    expr = compile_udf(fn, args, dt)
                    out_specs.append(("compiled", expr, name, dt))
                    continue
                except (UdfCompileError, AN.AnalysisException):
                    pass  # outside the subset → arrow bridge
            udfs.append(PyUDFSpec(fn, args, dt, vectorized, name))
            out_specs.append(("udf", len(udfs) - 1, name, dt))
        ext_fields = (list(base_schema.fields)
                      + [T.StructField(f"_udf{i}", u.dtype, True)
                         for i, u in enumerate(udfs)])
        ext_schema = T.StructType(tuple(ext_fields))
        plan = (L.PythonEval(self._plan, udfs, ext_schema) if udfs
                else self._plan)
        exprs, fields = [], []
        for spec in out_specs:
            if spec[0] == "plain":
                c = spec[1]
                if isinstance(c, str) and c == "*":
                    for i, f in enumerate(base_schema.fields):
                        exprs.append(BoundReference(i, f.dtype,
                                                    f.nullable))
                        fields.append(f)
                    continue
                u = _to_column(c)._u
                e = AN.resolve(u, ext_schema)
                exprs.append(e)
                fields.append(T.StructField(self._output_name(u, e),
                                            e.dtype))
            elif spec[0] == "compiled":
                _, e, name, dt = spec
                exprs.append(e)
                fields.append(T.StructField(name, dt, True))
            else:
                _, i, name, dt = spec
                exprs.append(BoundReference(nc + i, dt, True))
                fields.append(T.StructField(name, dt, True))
        return self._derive_ctor( L.Project(
            plan, exprs, T.StructType(tuple(fields))))

    def mapInPandas(self, fn, schema) -> "DataFrame":
        """fn(iterator[pandas.DataFrame]) → iterator[pandas.DataFrame]
        with the declared output schema [REF: GpuMapInPandasExec]."""
        if not isinstance(schema, T.StructType):
            raise AN.AnalysisException(
                "mapInPandas needs a StructType output schema")
        return self._derive_ctor(
                         L.MapInPandas(self._plan, fn, schema))

    @staticmethod
    def _window_u(c) -> Optional[UExpr]:
        """The window UExpr under an optional alias, else None."""
        if isinstance(c, str):
            return None
        u = _to_column(c)._u
        core = u.children[0] if u.op == "alias" else u
        return core if core.op == "window" else None

    def _select_with_windows(self, cols) -> "DataFrame":
        """Spark's ExtractWindowExpressions analog: insert Window plan
        nodes (one per distinct spec) that append the computed columns,
        then project the requested output."""
        from spark_rapids_tpu.ops.expressions import BoundReference
        base_schema = self.schema
        plan = self._plan
        appended = {}   # id(col-obj position) → (field index in extended)
        groups = {}     # spec-key → (pby, orders, [fns], [positions])
        out_specs = []  # per output col: ("plain", u) | ("win", pos_key)
        for ci, c in enumerate(cols):
            wu = self._window_u(c)
            if wu is None:
                out_specs.append(("plain", c))
                continue
            u = _to_column(c)._u
            pby, orders, wf, default_name = AN.resolve_window(
                wu, base_schema)
            alias = u.payload if u.op == "alias" else None
            skey = repr((wu.payload.partition_by, wu.payload.order_by,
                         wu.payload.frame))
            g = groups.setdefault(skey, (pby, orders, [], []))
            g[2].append(wf)
            g[3].append(ci)
            out_specs.append(("win", (skey, len(g[2]) - 1),
                             alias or default_name, wf.dtype))
        # build the Window chain; track where each group's outputs land
        offsets = {}
        ext_fields = list(base_schema.fields)
        wcount = 0
        for skey, (pby, orders, fns, _) in groups.items():
            offsets[skey] = len(ext_fields)
            new_fields = [
                T.StructField(f"_w{wcount + i}", fn.dtype)
                for i, fn in enumerate(fns)]
            wcount += len(fns)
            ext_fields.extend(new_fields)
            plan = L.Window(
                plan, pby, orders, fns,
                T.StructType(tuple(ext_fields)))
        ext_schema = T.StructType(tuple(ext_fields))
        # final projection over the extended schema
        exprs, fields = [], []
        for spec in out_specs:
            if spec[0] == "plain":
                c = spec[1]
                if isinstance(c, str) and c == "*":
                    for i, f in enumerate(base_schema.fields):
                        exprs.append(BoundReference(i, f.dtype,
                                                    f.nullable))
                        fields.append(f)
                    continue
                u = _to_column(c)._u
                e = AN.resolve(u, ext_schema)
                exprs.append(e)
                fields.append(T.StructField(self._output_name(u, e),
                                            e.dtype))
            else:
                (skey, j), name, dtype = spec[1], spec[2], spec[3]
                idx = offsets[skey] + j
                exprs.append(BoundReference(idx, dtype, True))
                fields.append(T.StructField(name, dtype))
        return self._derive_ctor( L.Project(
            plan, exprs, T.StructType(tuple(fields))))

    @staticmethod
    def _output_name(u: UExpr, e) -> str:
        if u.op == "alias":
            return u.payload
        if u.op == "attr":
            return u.payload
        return str(e)

    def _logical_columns(self) -> List[str]:
        """Column names as the user sees them: struct fields collapse to
        the struct name (positioned at its first physical field)."""
        out = []
        phys_to_struct = {}
        for sname, spec in self._structs.items():
            for p in spec.phys_cols:
                phys_to_struct[p] = sname
        seen = set()
        for n in self.columns:
            sname = phys_to_struct.get(n)
            if sname is None:
                out.append(n)
            elif sname not in seen:
                seen.add(sname)
                out.append(sname)
        return out

    def withColumn(self, name: str, c: Column) -> "DataFrame":
        cols = self._logical_columns()
        if name in cols:  # replace in place (pyspark semantics)
            return self.select(*[(c.alias(name) if n == name else n)
                                 for n in cols])
        return self.select(*cols, c.alias(name))

    def withColumnRenamed(self, old: str, new: str) -> "DataFrame":
        cols = [(_col(n).alias(new) if n == old else n)
                for n in self._logical_columns()]
        return self.select(*cols)

    def drop(self, *names) -> "DataFrame":
        keep = [n for n in self._logical_columns() if n not in names]
        return self.select(*keep)

    def filter(self, condition: Union[str, Column]) -> "DataFrame":
        if isinstance(condition, str):
            raise NotImplementedError("SQL-string filters not yet supported")
        cond = AN.resolve(self._rewrite_struct_ref(condition._u),
                          self.schema)
        if not isinstance(cond.dtype, (T.BooleanType, T.NullType)):
            raise AN.AnalysisException(
                f"filter condition must be boolean, got {cond.dtype}")
        return self._derive_ctor( L.Filter(self._plan, cond))

    where = filter

    def limit(self, n: int) -> "DataFrame":
        return self._derive_ctor( L.Limit(self._plan, n))

    def union(self, other: "DataFrame") -> "DataFrame":
        if len(other.schema) != len(self.schema):
            raise AN.AnalysisException("union: column count mismatch")
        return self._derive_ctor( L.Union([self._plan, other._plan]))

    unionAll = union

    def distinct(self) -> "DataFrame":
        return self.groupBy(*self.columns).agg()

    def sample(self, withReplacement=None, fraction=None, seed=None
               ) -> "DataFrame":
        """Bernoulli sample.  Accepts pyspark's signature variants:
        sample(fraction), sample(fraction, seed),
        sample(withReplacement, fraction, seed)."""
        if isinstance(withReplacement, float):
            # legacy form sample(fraction[, seed]): shift the arguments —
            # an explicit seed= keyword wins over the positional slot
            s2 = seed if seed is not None else fraction
            withReplacement, fraction, seed = (
                False, withReplacement, None if s2 is None else int(s2))
        if withReplacement:
            raise NotImplementedError(
                "sample(withReplacement=True) is not supported")
        if fraction is None or not (0.0 <= fraction <= 1.0):
            raise AN.AnalysisException(
                f"sample fraction must be in [0, 1], got {fraction}")
        if seed is None:
            import random
            seed = random.randint(0, 2**31 - 1)
        return self._derive_ctor(
                         L.Sample(self._plan, float(fraction), int(seed)))

    def repartition(self, num: int, *cols) -> "DataFrame":
        keys = [AN.resolve(_to_column(c)._u, self.schema) for c in cols] or None
        return self._derive_ctor(
                         L.Repartition(self._plan, num, keys))

    def groupBy(self, *cols) -> "GroupedData":
        exprs = []
        names = []
        for c in self._expand_struct_names(cols):
            u = self._rewrite_struct_ref(_to_column(c)._u)
            e = AN.resolve(u, self.schema)
            exprs.append(e)
            names.append(self._output_name(u, e))
        return GroupedData(self, exprs, names)

    groupby = groupBy

    def rollup(self, *cols) -> "GroupedData":
        """Hierarchical grouping sets: (a,b), (a), () for rollup(a, b).
        [REF: GpuExpandExec.scala — the reference accelerates Spark's
        Expand+Aggregate rollup plan; same shape here]"""
        g = self.groupBy(*cols)
        nk = len(g.grouping)
        g.sets = [list(range(k)) for k in range(nk, -1, -1)]
        return g

    def cube(self, *cols) -> "GroupedData":
        """All 2^n grouping-set combinations."""
        import itertools
        g = self.groupBy(*cols)
        nk = len(g.grouping)
        g.sets = [list(s) for r in range(nk, -1, -1)
                  for s in itertools.combinations(range(nk), r)]
        return g

    def agg(self, *aggs) -> "DataFrame":
        return GroupedData(self, [], []).agg(*aggs)

    def orderBy(self, *cols, ascending=None) -> "DataFrame":
        # ``ascending`` aligns with the USER's argument list; struct
        # expansion happens after, each field inheriting its struct's
        # direction (Spark orders structs field-lexicographically)
        pairs = []
        for i, c in enumerate(cols):
            a = (None if ascending is None
                 else (ascending[i] if isinstance(ascending, (list, tuple))
                       else bool(ascending)))
            sname = self._struct_name_of(c)
            if sname is not None:
                pairs.extend((p, a) for p in
                             self._structs[sname].phys_cols)
            else:
                pairs.append((c, a))
        orders = []
        for c, a in pairs:
            u = self._rewrite_struct_ref(_to_column(c)._u)
            asc, nulls_first = True, True
            if u.op == "sortorder":
                direction, nulls = u.payload
                asc = direction == "asc"
                nulls_first = nulls == "nulls_first"
                u = u.children[0]
            if a is not None:
                asc = a
                nulls_first = asc
            e = AN.resolve(u, self.schema)
            orders.append(L.SortOrder(e, asc, nulls_first))
        return self._derive_ctor( L.Sort(self._plan, orders))

    sort = orderBy

    def join(self, other: "DataFrame", on=None, how: str = "inner"
             ) -> "DataFrame":
        how = {"inner": "inner", "left": "left", "leftouter": "left",
               "left_outer": "left", "right": "right",
               "rightouter": "right", "right_outer": "right",
               "outer": "full", "full": "full", "fullouter": "full",
               "full_outer": "full", "semi": "left_semi",
               "leftsemi": "left_semi", "left_semi": "left_semi",
               "anti": "left_anti", "leftanti": "left_anti",
               "left_anti": "left_anti", "cross": "cross"}[how.lower()]
        if on is None:
            on = []
        if isinstance(on, str):
            on = [on]
        if isinstance(on, Column):
            return self._expression_join(other, on, how)
        left_keys, right_keys = [], []
        using = all(isinstance(c, str) for c in on)
        if not using:
            raise AN.AnalysisException(
                "join 'on' must be a column-name list or a single Column "
                "condition")
        for name in on:
            left_keys.append(AN.resolve(UExpr("attr", name), self.schema))
            right_keys.append(AN.resolve(UExpr("attr", name),
                                         other.schema))
        # output schema: USING semantics — join cols once (from left), then
        # remaining left cols, then remaining right cols
        fields: List[T.StructField] = []
        if using:
            for name in on:
                f = self.schema.fields[self.schema.field_index(name)]
                nullable = f.nullable or how in ("right", "full")
                fields.append(T.StructField(name, f.dtype, nullable))
            for f in self.schema.fields:
                if f.name not in on:
                    nullable = f.nullable or how in ("right", "full")
                    fields.append(T.StructField(f.name, f.dtype, nullable))
            if how not in ("left_semi", "left_anti"):
                for f in other.schema.fields:
                    if f.name not in on:
                        nullable = f.nullable or how in ("left", "full")
                        fields.append(T.StructField(f.name, f.dtype, nullable))
        schema = T.StructType(tuple(fields))
        out = self._derive_ctor(L.Join(
            self._plan, other._plan, how, left_keys, right_keys, None,
            schema))
        return self._adopt_structs(out, other)

    def crossJoin(self, other: "DataFrame") -> "DataFrame":
        return self.join(other, on=[], how="cross")

    def _expression_join(self, other: "DataFrame", on: Column, how: str
                         ) -> "DataFrame":
        """Spark's ExtractEquiJoinKeys analog: resolve the condition
        against left++right, pull `left.col == right.col` conjuncts out
        as equi keys, keep the rest as a residual condition evaluated
        over the join output."""
        from spark_rapids_tpu.ops import expressions as E
        nl = len(self.schema)
        combined = T.StructType(tuple(self.schema.fields)
                                + tuple(other.schema.fields))
        cond = AN.resolve(on._u, combined)
        if not isinstance(cond.dtype, (T.BooleanType, T.NullType)):
            raise AN.AnalysisException(
                f"join condition must be boolean, got {cond.dtype}")

        conjuncts: List = []

        def split(e):
            if isinstance(e, E.And):
                split(e.left)
                split(e.right)
            else:
                conjuncts.append(e)

        split(cond)
        left_keys, right_keys, residual = [], [], []
        for c in conjuncts:
            sides = None
            if (isinstance(c, E.EqualTo)
                    and isinstance(c.left, E.BoundReference)
                    and isinstance(c.right, E.BoundReference)):
                li, ri = c.left.index, c.right.index
                if li < nl <= ri:
                    sides = (li, ri - nl)
                elif ri < nl <= li:
                    sides = (ri, li - nl)
            if sides is None:
                residual.append(c)
                continue
            li, ri = sides
            lf = self.schema.fields[li]
            rf = other.schema.fields[ri]
            left_keys.append(E.BoundReference(li, lf.dtype, lf.nullable))
            right_keys.append(E.BoundReference(ri, rf.dtype, rf.nullable))
        res = None
        for c in residual:
            res = c if res is None else E.And(res, c)
        if not left_keys and how not in ("inner", "cross"):
            raise AN.AnalysisException(
                f"{how} join requires at least one equi-join conjunct "
                "(left.col == right.col); got only a non-equi condition")
        # expression-join output: ALL left cols ++ ALL right cols
        semi = how in ("left_semi", "left_anti")
        fields: List[T.StructField] = []
        for f in self.schema.fields:
            nullable = f.nullable or how in ("right", "full")
            fields.append(T.StructField(f.name, f.dtype, nullable))
        if not semi:
            for f in other.schema.fields:
                nullable = f.nullable or how in ("left", "full")
                fields.append(T.StructField(f.name, f.dtype, nullable))
        out = self._derive_ctor(L.Join(
            self._plan, other._plan, how, left_keys, right_keys, res,
            T.StructType(tuple(fields)), using=False))
        return self._adopt_structs(out, other)

    # -- actions ------------------------------------------------------------
    def _execute_plan(self):
        from spark_rapids_tpu.plan.optimizer import optimize
        from spark_rapids_tpu.runtime import trace
        conf = self.session.rapids_conf()
        with trace.span("Plan", "optimize"):
            logical = optimize(self._plan, conf)
        with trace.span("Plan", "physicalPlan"):
            cpu = plan_physical(logical, conf)
        with trace.span("Plan", "overrides"):
            result = apply_overrides(cpu, conf)
        self._last_override = result
        return result.plan

    def fallback_summary(self) -> dict:
        """Device-vs-fallback operator counts for the last planned
        execution (the reference's explain=NOT_ON_GPU signal as a
        metric [REF: ExplainPlanImpl; SURVEY §5.5])."""
        res = getattr(self, "_last_override", None)
        if res is None:
            self._execute_plan()
            res = self._last_override
        return res.fallback_summary()

    def toArrow(self, timeout_ms: Optional[float] = None,
                query_id: Optional[int] = None,
                cancel_token=None,
                tenant: Optional[str] = None,
                books=None) -> pa.Table:
        """Execute and return the result as an Arrow table.

        ``timeout_ms`` puts an in-process deadline on THIS execution
        (overriding ``spark.rapids.tpu.query.timeoutMs``): when it
        expires, every blocking boundary raises
        ``QueryCancelled(reason="deadline")`` and the engine reclaims
        the query's resources before the exception reaches the
        caller.

        ``query_id``/``cancel_token`` are the ``QueryServer``'s
        plumbing: the server mints the id and registers the token at
        *submit* time (so the query is cancellable while still queued
        for a run slot), then the admitted worker passes both here and
        the execution adopts them instead of minting fresh ones.
        ``books`` is what ``open_books`` returned on the worker thread
        before the query had its run slot (so that the wait is in
        them): adopted and closed here like books opened here.
        ``tenant`` folds the tenant's conf overrides into the result
        key so tenants never share a cache slot.

        With ``spark.rapids.tpu.cache.enabled``, the result cache is
        consulted first: a hit hands back the resident Arrow table —
        no partition pump, no device semaphore — while still running
        the full query-window machinery, so the event-log entry
        carries ``cache.status="hit"`` with its usual telemetry/
        semaphore/stats attribution."""
        import contextlib
        import time as _time
        from spark_rapids_tpu import conf as C
        from spark_rapids_tpu.runtime import cancel as cancel_mod
        from spark_rapids_tpu.runtime import stats as stats_mod
        from spark_rapids_tpu.runtime import telemetry
        from spark_rapids_tpu.runtime import trace
        conf = self.session.rapids_conf()
        qid = query_id if query_id is not None else trace.next_query_id()
        stack = contextlib.ExitStack()
        profile_dir = None
        if conf.get(C.PROFILE_ENABLED):
            # per-query xplane capture, dump dir named after the query id
            # so trace + event-log entries cross-link
            # [REF: spark-rapids-jni profiler].  Entered before the
            # tracer starts: the tracer then mirrors its spans into it
            import jax
            import os
            profile_dir = os.path.join(str(conf.get(C.PROFILE_PATH)),
                                       f"query-{qid:06d}")
            os.makedirs(profile_dir, exist_ok=True)
            stack.enter_context(jax.profiler.trace(profile_dir))
        from spark_rapids_tpu.runtime import attribution as attr_mod
        if books is None:
            tracer, arec = open_books(conf, qid)
        else:
            tracer, arec = books
            if tracer is not None and profile_dir is not None:
                tracer.mirror = True   # opened before the session above
        # the root opens before planning: the wall the books close on
        # is the caller's, plan included
        root = (tracer.begin("Query", "execute")
                if tracer is not None else None)
        plan = None
        cache_store = ckey = None
        qwin = rwin = cwin = collector = None
        scoped = False
        error = None
        cancelled = None
        cache_info = None
        flight = None
        try:
            plan = self._execute_plan()
            self._last_plan = plan
            if conf.get(C.CACHE_ENABLED):
                from spark_rapids_tpu import cache as cache_mod
                cache_store = cache_mod.get_cache(conf)
                try:
                    ckey = cache_mod.result_key(self._plan, plan, conf,
                                                tenant=tenant)
                except Exception:
                    # unkeyable inputs (e.g. a vanished scan file) —
                    # execute uncached
                    cache_store = None
            qwin = telemetry.begin_query(qid)
            from spark_rapids_tpu.runtime import resilience
            rwin = resilience.begin_query(qid)
            cwin = cancel_mod.begin_query(qid, conf, timeout_ms=timeout_ms,
                                          token=cancel_token)
            scoped = True
            if conf.get(C.STATS_ENABLED):
                collector = stats_mod.start_query(
                    qid, level=str(conf.get(C.STATS_LEVEL)),
                    skew_threshold=float(
                        conf.get(C.STATS_SKEW_THRESHOLD)))
            served = None
            if cache_store is not None:
                with trace.span("ResultCache", "cacheProbe"):
                    served = cache_store.lookup(ckey.key)
                    if served is None:
                        role, fl = cache_store.join_flight(ckey.key)
                        if role == "leader":
                            flight = fl
                            fl.leader_qid = qid
                        else:
                            # another execution of this exact key is
                            # in progress — wait for it, then
                            # re-probe; compute ourselves if it
                            # failed or skipped
                            tok = cancel_mod.current()
                            while not fl.done.wait(0.05):
                                cancel_mod.check()
                                if tok is not None:
                                    tok.preempt_point()
                                lq = fl.leader_qid
                                lt = (cancel_mod.get_token(lq)
                                      if lq is not None else None)
                                if (lt is not None
                                        and lt.preempt_pending()):
                                    # the leader was preempted
                                    # mid-flight; followers waiting
                                    # on it while holding run slots
                                    # would starve the scheduler of
                                    # the very slot the leader needs
                                    # to resume — break away and
                                    # compute independently
                                    break
                            served = cache_store.lookup(ckey.key)
                            if served is not None:
                                cache_info = {"coalesced": True}
            if served is not None:
                out = served.value
                cache_info = {
                    "status": "hit", "key": served.key,
                    "signature": served.sig,
                    "bytes": served.nbytes,
                    "saved_s": round(served.runtime_s, 6),
                    "age_s": round(
                        _time.monotonic() - served.created, 6),
                    **(cache_info or {})}
            else:
                t_exec = _time.perf_counter()
                tables = self._pump_partitions(plan, conf)
                with trace.span("Result", "resultConcat"):
                    if not tables:
                        out = self._reassemble_structs(pa.table(
                            {f.name: pa.array(
                                [], type=T.to_arrow(f.dtype))
                             for f in self.schema.fields}))
                    else:
                        out = self._reassemble_structs(
                            pa.concat_tables(tables))
                if cache_store is not None:
                    runtime_s = _time.perf_counter() - t_exec
                    cache_store.note_miss()
                    with trace.span("ResultCache", "cacheServe"):
                        stored = cache_store.put(
                            ckey, out, out.nbytes, runtime_s)
                    cache_info = {
                        "key": ckey.key, "signature": ckey.sig,
                        "bytes": out.nbytes,
                        "runtime_s": round(runtime_s, 6), **stored}
        except cancel_mod.QueryCancelled as e:
            cancelled = e
            error = f"{type(e).__name__}: {e}"
            # guaranteed reclamation: the cancelled pump abandoned its
            # registered spillables mid-flight — close them all so HBM
            # accounting unwinds and disk spill files are unlinked
            # (report_leaks() returns 0 after every cancelled query)
            from spark_rapids_tpu.runtime import memory
            mgr = memory.peek_manager()
            if mgr is not None:
                mgr.reclaim_all()
            raise
        except BaseException as e:
            error = f"{type(e).__name__}: {e}"
            raise
        finally:
            try:
                if flight is not None:
                    # wake single-flight followers even on failure —
                    # they re-probe and compute for themselves
                    cache_store.finish_flight(ckey.key, flight)
                if root is not None:
                    tracer.end(root)
                trace.end_query(tracer)
                stats_mod.end_query(collector)
                attr_mod.end_query(arec)
                if scoped:
                    cancel_mod.finish_query(cwin)
                    # the epilogue runs on the caller's clock after the
                    # answer exists: timed (and mirrored) as its own
                    # span, outside the wall the ledger closes on
                    rec = (tracer.begin("Query", "record")
                           if tracer is not None else None)
                    book = self._record_query(
                        qid, tracer, conf, profile_dir, error, qwin,
                        rwin, cancelled=cancelled, ctoken=cwin,
                        collector=collector, cache_info=cache_info,
                        recorder=arec)
                    if rec is not None:
                        tracer.end(rec)
                        if book is not None:
                            book["record_s"] = round(rec.dur, 6)
                # else: planning failed — nothing ran, nothing to record
            finally:
                stack.close()
        return out

    def _record_query(self, qid, tracer, conf, profile_dir, error,
                      qwin=None, rwin=None, cancelled=None, ctoken=None,
                      collector=None, cache_info=None, recorder=None):
        """One event-log entry per execution: plan tree, device/fallback
        report, all metrics at their levels, span rollup, artifact
        cross-links — the reference's driver-log plan-conversion report,
        machine-readable.  Returns the ledger as published to
        ``attribution.recent()`` (None when attribution is off)."""
        import time as _time
        from spark_rapids_tpu import conf as C
        from spark_rapids_tpu.runtime import trace
        plan = self._last_plan
        override = getattr(self, "_last_override", None)
        entry = {
            "query_id": qid,
            "ts": _time.strftime("%Y-%m-%dT%H:%M:%S%z"),
            "status": ("cancelled" if cancelled is not None
                       else "error" if error else "ok"),
            "plan": plan.tree_string(),
            "metrics": trace.plan_metrics(plan),
        }
        if error:
            entry["error"] = error
        if cache_info is not None:
            # result-cache outcome: status hit|stored|uncached, with
            # key/signature/bytes and saved_s (hit) or runtime_s (miss)
            entry["cache"] = cache_info
        if cancelled is not None:
            cinfo = {"reason": cancelled.reason}
            if ctoken is not None:
                if ctoken.latency_s is not None:
                    cinfo["latency_s"] = round(ctoken.latency_s, 6)
                if ctoken.detail:
                    cinfo["detail"] = ctoken.detail
            entry["cancel"] = cinfo
        if override is not None:
            entry["fallback"] = override.fallback_summary()
            entry["fallback_report"] = override.fallback_report()
        if tracer is not None and conf.get(C.TRACE_ENABLED):
            # the rollup only when the user asked for tracing — an
            # attribution-only tracer feeds the ledger below but must
            # not start emitting rollups
            entry["wall_s"] = round(tracer.wall_s, 6)
            rollup = tracer.rollup()
            entry["op_rollup"] = rollup
            entry["dropped_spans"] = tracer.dropped
            self._last_rollup = rollup
        attribution = None
        if tracer is not None and conf.get(C.ATTRIBUTION_ENABLED):
            from spark_rapids_tpu.runtime import attribution as attr_mod
            attribution = attr_mod.publish(attr_mod.attribute(tracer),
                                           tracer)
            entry["attribution"] = attribution
            attr_mod.note_unaccounted(attribution["unaccounted_s"])
        if profile_dir:
            entry["profile_dir"] = profile_dir
        lore = str(conf.get(C.LORE_TAG))
        if lore:
            entry["lore_tag"] = lore
        if qwin is not None:
            # process-counter deltas this query contributed + health
            # verdicts over them — cross-linked by the same query_id as
            # the trace/profile artifacts
            from spark_rapids_tpu.runtime import telemetry
            deltas, elapsed = qwin.finish()
            entry["telemetry"] = deltas
            health = telemetry.evaluate_health(deltas, elapsed, conf,
                                               query_id=qid)
            if health:
                entry["health"] = health
        from spark_rapids_tpu.runtime.semaphore import peek_semaphore
        sem = peek_semaphore()
        if sem is not None:
            # close THIS query's keyed stats window (opened by
            # telemetry.begin_query) — under concurrency the legacy
            # process-wide max_holders/wait_time bleed across queries,
            # the keyed window doesn't
            sw = sem.end_query_stats(qid)
            if sw is not None:
                entry["semaphore"] = {
                    "max_holders": sw["max_holders"],
                    "wait_s": round(sw["wait_time"], 6)}
        # retry/breaker/degradation rollup for the query's failure
        # domains (see runtime/resilience.py).  A query that joined
        # another's scope (rwin None) closes its share of it too, or
        # the scope's depth never returns to 0
        from spark_rapids_tpu.runtime import resilience
        res = resilience.finish_query(rwin)
        if res is not None:
            entry["resilience"] = res
            # runtime degradations join the plan-time fallback
            # report: the same "what did NOT run on device" story,
            # one decided at planning, one at execution
            if res["degraded_ops"]:
                entry.setdefault("fallback_report", []).extend(
                    f"!{d['op']} degraded to the host path at "
                    f"runtime [{d['domain']}] because {d['cause']}"
                    for d in res["degraded_ops"])
        if collector is not None:
            # the stats plane's profile record: per-op observed stats
            # keyed by stable plan-node signatures + exchange skew
            # summary, joined with the trace rollup's self-times
            from spark_rapids_tpu.runtime import stats as stats_mod
            profile = collector.report(
                plan, rollup=entry.get("op_rollup"),
                wall_s=entry.get("wall_s"))
            profile["ts"] = entry["ts"]
            profile["status"] = entry["status"]
            if attribution is not None:
                profile["attribution"] = attribution
            entry["op_stats"] = profile["ops"]
            if profile["exchanges"]:
                entry["exchange_stats"] = profile["exchanges"]
            if profile.get("adaptive_decisions"):
                entry["adaptive_decisions"] = (
                    profile["adaptive_decisions"])
            self._last_profile = profile
            self.session._last_profile = profile
            store = str(conf.get(C.STATS_STORE_PATH))
            if store:
                stats_mod.append_profile(store, profile)
        if recorder is not None:
            # bad exit -> leave the black box: the ring + ledger survive
            # the query that died.  Triggers: deadline kill, explicit
            # cancel, error, or a health WARN on an otherwise-ok run.
            trigger = None
            if cancelled is not None:
                trigger = ("timeout" if cancelled.reason == "deadline"
                           else "cancel")
            elif error:
                trigger = "error"
            elif entry.get("health"):
                trigger = "health"
            bb_dir = str(conf.get(C.ATTRIBUTION_BLACKBOX_PATH))
            if trigger is not None and bb_dir:
                from spark_rapids_tpu.runtime import (
                    attribution as attr_mod)
                extra = {k: entry[k] for k in
                         ("status", "error", "cancel", "health")
                         if entry.get(k)}
                path = attr_mod.dump_blackbox(
                    bb_dir, qid, trigger, attribution=attribution,
                    recorder=recorder, extra=extra)
                if path:
                    entry["blackbox"] = path
        self._last_query_entry = entry
        self.session._record_query(entry)
        log_path = str(conf.get(C.QUERY_LOG_PATH))
        if log_path:
            trace.append_query_log(log_path, entry)
        return attribution

    def _reassemble_structs(self, t: pa.Table) -> pa.Table:
        """Physical flattened columns → logical arrow struct columns
        (the inverse of session._decompose_structs)."""
        if not self._structs:
            return t
        for sname, spec in self._structs.items():
            names = t.column_names
            if not all(p in names for _, p in spec.fields):
                continue
            def one_chunk(c):
                if isinstance(c, pa.ChunkedArray):
                    if c.num_chunks == 0:
                        return pa.array([], type=c.type)
                    return pa.concat_arrays(c.chunks)
                return c

            children = [one_chunk(t.column(p)) for _, p in spec.fields]
            mask = None
            if spec.null_col and spec.null_col in names:
                mask = pa.array(
                    one_chunk(t.column(spec.null_col)).to_pylist(),
                    pa.bool_())
            sa = pa.StructArray.from_arrays(
                children, names=[f for f, _ in spec.fields], mask=mask)
            pos = names.index(spec.fields[0][1])
            drop = {p for _, p in spec.fields}
            if spec.null_col:
                drop.add(spec.null_col)
            arrays, outnames = [], []
            inserted = False
            for n in names:
                if n == spec.fields[0][1]:
                    arrays.append(sa)
                    outnames.append(sname)
                    inserted = True
                if n in drop:
                    continue
                arrays.append(t.column(n))
                outnames.append(n)
            assert inserted
            t = pa.table(dict(zip(outnames, arrays)))
        return t

    @staticmethod
    def _pump_partitions(plan, conf) -> List[pa.Table]:
        """Execute every partition; partitions run on a thread pool (the
        Spark-task-slot analog) and device-touching plans must hold the
        admission semaphore [REF: GpuSemaphore.scala] — permits =
        ``spark.rapids.sql.concurrentGpuTasks``."""
        from spark_rapids_tpu.exec.base import TpuExec

        def has_device_work(node) -> bool:
            return isinstance(node, TpuExec) or any(
                has_device_work(c) for c in node.children)

        nparts = plan.num_partitions()
        on_device = has_device_work(plan)

        from spark_rapids_tpu.runtime import trace as trace_mod

        def pump(p: int) -> List[pa.Table]:
            # per-partition envelope span: charges iterator plumbing
            # (time between instrumented stages) to the pump_idle
            # bucket, the root's arrow conversion to result_d2h — a
            # no-op when neither tracing nor attribution is active
            args = {"partition": p}
            with trace_mod.span("PumpTask", "pumpTask", args):
                out = []
                for b in plan.execute(p):
                    with trace_mod.span("Result", "resultD2H", args):
                        out.append(H.to_arrow_table(b))
                return out

        if not on_device:
            out = []
            for p in range(nparts):
                out.extend(pump(p))
            return out

        from spark_rapids_tpu import conf as C
        from spark_rapids_tpu.runtime import cancel as cancel_mod
        from spark_rapids_tpu.runtime import scheduler as sched_mod
        waits: List[float] = []  # this query's waits only

        parts = list(range(nparts))
        from spark_rapids_tpu.parallel.executor import get_executor
        if get_executor() is not None:
            # multi-executor: every process must enter each collective
            # in the SAME order — materialize exchanges sequentially
            # (children-first = execution-dependency order) before the
            # parallel pump, then pump only partitions whose mesh device
            # is local to this process
            from spark_rapids_tpu.exec.distributed import (
                TpuIciShuffleExchangeExec, owned_partitions)

            def pre_materialize(node):
                for c in node.children:
                    pre_materialize(c)
                if isinstance(node, TpuIciShuffleExchangeExec):
                    node._materialize()

            with sched_mod.device_hold(conf, waited_out=waits):
                pre_materialize(plan)
            parts = owned_partitions(plan)

        # the query's cancel scope is thread-local — capture the token
        # here (the query thread) and re-bind it inside each pump-pool
        # worker so device admission stays cancellable and wait time
        # attributes to the right query under concurrency
        tok = cancel_mod.current()

        def task(p: int) -> List[pa.Table]:
            with cancel_mod.bind(tok), \
                    sched_mod.device_hold(conf, waited_out=waits):
                return pump(p)

        # a single task still holds a permit — a 1-partition query must
        # count against the concurrency cap like any other; the pump
        # pool records queue depth + per-task latency either way
        from spark_rapids_tpu.parallel.executor import run_pump_tasks
        permits = int(conf.get(C.CONCURRENT_TASKS) or 2)
        workers = min(len(parts), max(permits * 2, 4))
        chunks = run_pump_tasks(task, parts, max_workers=workers)
        plan.metric("semaphoreWaitTime").add(sum(waits))
        return [t for chunk in chunks for t in chunk]

    def metrics(self, level: Optional[str] = None):
        """Operator metrics of the last execution, filtered by
        ``spark.rapids.sql.metrics.level`` (or an explicit level)."""
        plan = getattr(self, "_last_plan", None)
        if plan is None:
            raise RuntimeError("no execution yet — run collect()/toArrow()")
        if level is None:
            from spark_rapids_tpu import conf as C
            level = self.session.rapids_conf().get(C.METRICS_LEVEL)
        return plan.collect_metrics(level=str(level))

    def collect(self, timeout_ms: Optional[float] = None) -> List[Row]:
        """Collect rows; ``timeout_ms`` deadlines the execution
        in-process (``QueryCancelled(reason="deadline")`` on expiry)."""
        tbl = self.toArrow(timeout_ms=timeout_ms)
        names = tuple(tbl.column_names)
        cols = [tbl.column(i).to_pylist() for i in range(tbl.num_columns)]
        return [Row(vals, names) for vals in zip(*cols)] if cols else []

    def count(self) -> int:
        return self.toArrow().num_rows

    def toPandas(self):
        return self.toArrow().to_pandas()

    def show(self, n: int = 20, truncate: bool = True):
        print(self.limit(n).toArrow().to_pandas().to_string())

    def explain(self, extended: bool = False):
        """``explain()`` prints the physical plan; ``explain(True)`` adds
        the fallback report; ``explain("metrics")`` prints the last
        execution's per-node metrics (at the configured level) and, when
        tracing was on, the per-operator self/total-time rollup;
        ``explain("analyze")`` EXECUTES the query if needed and prints
        the plan tree annotated with the observed per-operator stats
        (rows/batches/bytes, exchange skew) + trace self-times."""
        if isinstance(extended, str) and extended.lower() == "metrics":
            return self._explain_metrics()
        if isinstance(extended, str) and extended.lower() == "analyze":
            return self._explain_analyze()
        from spark_rapids_tpu.plan.optimizer import optimize
        conf = self.session.rapids_conf()
        cpu = plan_physical(optimize(self._plan, conf), conf)
        result = apply_overrides(cpu, conf)
        print(result.plan.tree_string())
        if extended:
            for line in result.fallback_report():
                print(line)

    def _explain_metrics(self):
        plan = getattr(self, "_last_plan", None)
        if plan is None:
            print("<no execution yet — run collect()/toArrow() first>")
            return
        print(plan.tree_string())
        for op, vals in self.metrics():
            shown = {k: (round(v, 6) if isinstance(v, float) else v)
                     for k, v in vals.items()}
            print(f"  {op}: {shown}")
        rollup = getattr(self, "_last_rollup", None)
        if rollup:
            print("-- per-op time attribution (traced) --")
            for op, r in sorted(rollup.items(),
                                key=lambda kv: -kv[1]["self_s"]):
                print(f"  {op}: self={r['self_s']:.6f}s "
                      f"total={r['total_s']:.6f}s spans={r['spans']}")

    @staticmethod
    def _fmt_bytes(n) -> str:
        n = float(n)
        for unit in ("B", "KiB", "MiB", "GiB"):
            if n < 1024 or unit == "GiB":
                return (f"{int(n)}{unit}" if unit == "B"
                        else f"{n:.1f}{unit}")
            n /= 1024
        return f"{n:.1f}GiB"

    def _explain_analyze(self):
        """EXPLAIN ANALYZE: run the query (with stats + tracing forced
        on when it has not executed with stats yet), then print the
        plan tree with each operator's observed statistics."""
        profile = getattr(self, "_last_profile", None)
        if profile is None:
            from spark_rapids_tpu import conf as C
            saved = {}
            for key in (C.STATS_ENABLED.key, C.TRACE_ENABLED.key):
                saved[key] = self.session.conf.get(key, None)
                self.session.conf.set(key, True)
            try:
                self.toArrow()
            finally:
                for key, old in saved.items():
                    if old is None:
                        self.session.conf.unset(key)
                    else:
                        self.session.conf.set(key, old)
            profile = getattr(self, "_last_profile", None)
        if profile is None:
            # a concurrent query owns the collector (nested execution)
            print("<stats unavailable — another query owns the stats "
                  "plane; re-run when it finishes>")
            return
        plan = self._last_plan
        # synthetic per-member records of fused regions carry their
        # PRE-fusion paths, which can collide with real nodes of the
        # fused tree — the tree walk wants only real-node records
        by_path = {r["path"]: r for r in profile["ops"]
                   if "fused_region" not in r}
        lines = []

        def walk(node, path, depth):
            rec = by_path.get(path, {})
            ann = (f"rows={rec.get('rows_out', 0)} "
                   f"batches={rec.get('batches_out', 0)} "
                   f"bytes={self._fmt_bytes(rec.get('bytes_out', 0))}")
            if rec.get("self_s") is not None:
                ann += (f" self={rec['self_s']:.6f}s"
                        f" total={rec['total_s']:.6f}s")
            parts = rec.get("partition_rows",
                            rec.get("partition_bytes"))
            if parts is not None:
                ann += (f" partitions={len(parts)}"
                        f" skew={rec.get('skew_factor', 1.0):.2f}")
                if rec.get("skewed"):
                    ann += " SKEWED"
                if rec.get("executors", 1) > 1:
                    ann += f" executors={rec['executors']}"
            if rec.get("fused"):
                ann += " fused"
            if rec.get("region_ops"):
                ann += f" region_ops={rec['region_ops']}"
                if rec.get("region_compile_s") is not None:
                    ann += f" compile={rec['region_compile_s']:.6f}s"
            if rec.get("kernel_backend"):
                ann += f" kernel={rec['kernel_backend']}"
            if rec.get("adaptive"):
                labels = []
                for d in rec["adaptive"]:
                    kind = d.get("kind")
                    if kind == "skew-split":
                        labels.extend(
                            f"skew-split({k})"
                            for k in d.get("splits", ()) or ("?",))
                    elif kind == "batch-retarget":
                        labels.append(
                            f"batch-retarget({d.get('target_rows')})")
                    else:
                        labels.append(str(kind))
                ann += " adaptive=" + ",".join(labels)
            lines.append("  " * depth
                         + ("*" if node.is_tpu else "")
                         + node.node_string() + f"  [{ann}]")
            for i, c in enumerate(node.children):
                walk(c, f"{path}.{i}", depth + 1)

        walk(plan, "0", 0)
        print("\n".join(lines))
        if profile.get("wall_s") is not None:
            print(f"-- wall {profile['wall_s']:.6f}s "
                  f"(query {profile['query_id']}, "
                  f"stats level {profile['level']}) --")

    @property
    def write(self):
        from spark_rapids_tpu.io.readers import DataFrameWriter
        return DataFrameWriter(self)


class GroupedData:
    def __init__(self, df: DataFrame, grouping, names):
        self.df = df
        self.grouping = grouping
        self.names = names
        self.sets = None  # grouping sets (rollup/cube); None = plain

    @staticmethod
    def _pandas_agg_u(a):
        u = _to_column(a)._u
        core = u.children[0] if u.op == "alias" else u
        if core.op == "pyudf" and core.payload[2]:  # vectorized
            return u, core
        return None

    def agg(self, *aggs) -> DataFrame:
        from spark_rapids_tpu.ops.aggregates import CountDistinct
        if any(self._pandas_agg_u(a) is not None for a in aggs):
            return self._agg_in_pandas(aggs)
        fns = []
        names = []
        for a in aggs:
            fn, name = AN.resolve_aggregate(_to_column(a)._u, self.df.schema)
            fns.append(fn)
            names.append(name)
        if any(isinstance(f, CountDistinct) for f in fns):
            if self.sets is not None:
                raise AN.AnalysisException(
                    "count(DISTINCT) under rollup/cube is not yet "
                    "supported")
            return self._agg_distinct(fns, names)
        if self.sets is not None:
            return self._agg_grouping_sets(fns, names)
        fields = [T.StructField(n, g.dtype)
                  for n, g in zip(self.names, self.grouping)]
        fields += [T.StructField(n, f.result_dtype)
                   for n, f in zip(names, fns)]
        schema = T.StructType(tuple(fields))
        return self.df._derive(L.Aggregate(
            self.df._plan, self.grouping, fns, schema))

    def _agg_in_pandas(self, aggs) -> DataFrame:
        """Grouped-aggregate pandas UDFs [REF: GpuAggregateInPandasExec]
        — lowered onto the grouped-map bridge: each agg fn(*series) →
        scalar runs per group inside one applyInPandas wrapper (device
        co-partitioning and the arrow bridge come for free)."""
        import pandas as pd
        if self.sets is not None:
            raise AN.AnalysisException(
                "pandas-UDF aggregates under rollup/cube are not "
                "supported")
        if not self.names:
            # global pandas-UDF aggregate: one row — lower by grouping
            # on a constant key, then drop it
            from spark_rapids_tpu.sql.functions import lit
            return (self.df.withColumn("__g", lit(0))
                    .groupBy("__g").agg(*aggs).drop("__g"))
        child_names = set(self.df.schema.field_names())
        for n in self.names:
            if n not in child_names:
                raise AN.AnalysisException(
                    "pandas-UDF aggregates need plain column grouping "
                    f"keys (got expression {n!r})")
        specs = []
        for i, a in enumerate(aggs):
            got = self._pandas_agg_u(a)
            if got is None:
                raise AN.AnalysisException(
                    "cannot mix pandas-UDF aggregates with built-in "
                    "aggregate functions in one agg() — split into two "
                    "aggregations and join")
            u, core = got
            fn, dt, _vec, fname = core.payload
            out_name = u.payload if u.op == "alias" else f"{fname}_{i}"
            arg_names = []
            for cu in core.children:
                if cu.op != "attr" or cu.payload not in child_names:
                    raise AN.AnalysisException(
                        "pandas-UDF aggregate arguments must be plain "
                        "columns (pre-compute expressions with "
                        "withColumn)")
                arg_names.append(cu.payload)
            specs.append((fn, dt, out_name, arg_names))
        key_names = list(self.names)
        by_name = {f.name: f for f in self.df.schema.fields}
        fields = [T.StructField(n, by_name[n].dtype) for n in key_names]
        fields += [T.StructField(n, dt) for _, dt, n, _ in specs]
        schema = T.StructType(tuple(fields))

        def wrapper(pdf):
            row = {k: [pdf[k].iloc[0]] for k in key_names}
            for fn, _dt, name, arg_names in specs:
                row[name] = [fn(*[pdf[an] for an in arg_names])]
            return pd.DataFrame(row)

        return self.applyInPandas(wrapper, schema)

    def _agg_grouping_sets(self, fns, names) -> DataFrame:
        """rollup/cube → Expand + Aggregate(keys + grouping id) + drop-gid
        Project — Spark's ResolveGroupingAnalytics plan shape, which the
        reference accelerates via GpuExpandExec."""
        from spark_rapids_tpu.ops.expressions import BoundReference, Literal
        child_schema = self.df.schema
        nc = len(child_schema)
        nk = len(self.grouping)
        projections = []
        for s in self.sets:
            inc = set(s)
            proj = [BoundReference(i, f.dtype, f.nullable)
                    for i, f in enumerate(child_schema.fields)]
            for i, g in enumerate(self.grouping):
                proj.append(g if i in inc else Literal(None, g.dtype))
            # Spark grouping_id: bit (nk-1-i) set when key i is NOT in
            # the grouping set
            gid = sum(1 << (nk - 1 - i) for i in range(nk)
                      if i not in inc)
            proj.append(Literal(gid, T.IntegerT))
            projections.append(proj)
        ex_fields = (list(child_schema.fields)
                     + [T.StructField(f"_g{i}", g.dtype, True)
                        for i, g in enumerate(self.grouping)]
                     + [T.StructField("_gid", T.IntegerT, False)])
        expand = L.Expand(self.df._plan, projections,
                          T.StructType(tuple(ex_fields)))
        grouping = [BoundReference(nc + i, g.dtype, True)
                    for i, g in enumerate(self.grouping)]
        grouping.append(BoundReference(nc + nk, T.IntegerT, False))
        agg_fields = ([T.StructField(n, g.dtype, True)
                       for n, g in zip(self.names, self.grouping)]
                      + [T.StructField("_gid", T.IntegerT, False)]
                      + [T.StructField(n, f.result_dtype)
                         for n, f in zip(names, fns)])
        agg = L.Aggregate(expand, grouping, fns,
                          T.StructType(tuple(agg_fields)))
        # final projection drops the grouping id
        out_fields = ([T.StructField(n, g.dtype, True)
                       for n, g in zip(self.names, self.grouping)]
                      + [T.StructField(n, f.result_dtype)
                         for n, f in zip(names, fns)])
        exprs = ([BoundReference(i, g.dtype, True)
                  for i, g in enumerate(self.grouping)]
                 + [BoundReference(nk + 1 + i, f.result_dtype)
                    for i, f in enumerate(fns)])
        return self.df._derive(L.Project(
            agg, exprs, T.StructType(tuple(out_fields))))

    def _agg_distinct(self, fns, names) -> DataFrame:
        """count(DISTINCT x): Spark's RewriteDistinctAggregates shape —
        a dedup groupby on (keys, x) feeding a plain count.

        [REF: Spark RewriteDistinctAggregates; the reference accelerates
        the same two-level plan]"""
        from spark_rapids_tpu.ops.aggregates import CountDistinct
        from spark_rapids_tpu.ops.expressions import BoundReference
        if not all(isinstance(f, CountDistinct) for f in fns):
            raise AN.AnalysisException(
                "mixing distinct and non-distinct aggregates in one "
                "agg() is not yet supported")
        if len(fns) != 1:
            raise AN.AnalysisException(
                "multiple count(DISTINCT) aggregates in one agg() are "
                "not yet supported")
        fn = fns[0]
        nk = len(self.grouping)
        inner_fields = [T.StructField(f"k{i}", g.dtype)
                        for i, g in enumerate(self.grouping)]
        inner_fields.append(T.StructField("_dv", fn.child.dtype))
        inner_schema = T.StructType(tuple(inner_fields))
        inner = L.Aggregate(self.df._plan,
                            list(self.grouping) + [fn.child], [],
                            inner_schema)
        from spark_rapids_tpu.ops.aggregates import Count
        outer_grouping = [BoundReference(i, g.dtype)
                          for i, g in enumerate(self.grouping)]
        outer_fn = Count(BoundReference(nk, fn.child.dtype))
        fields = [T.StructField(n, g.dtype)
                  for n, g in zip(self.names, self.grouping)]
        fields.append(T.StructField(names[0], T.LongT))
        schema = T.StructType(tuple(fields))
        return self.df._derive(L.Aggregate(
            inner, outer_grouping, [outer_fn], schema))

    def count(self) -> DataFrame:
        from spark_rapids_tpu.sql import functions as F
        return self.agg(F.count("*").alias("count"))

    def applyInPandas(self, fn, schema) -> DataFrame:
        """Grouped-map pandas UDF: fn(pandas.DataFrame) → DataFrame per
        group.  Rides a hash exchange on the keys so one group never
        splits [REF: GpuFlatMapGroupsInPandasExec]."""
        from spark_rapids_tpu.ops.expressions import BoundReference
        if not isinstance(schema, T.StructType):
            raise AN.AnalysisException(
                "applyInPandas needs a StructType output schema")
        if self.sets is not None:
            raise AN.AnalysisException(
                "applyInPandas is not supported under rollup/cube")
        key_indices = []
        for g in self.grouping:
            if not isinstance(g, BoundReference):
                raise AN.AnalysisException(
                    "applyInPandas grouping keys must be plain columns")
            key_indices.append(g.index)
        nparts = self.df.session.rapids_conf().shuffle_partitions
        shuffled = L.Repartition(self.df._plan, nparts,
                                 list(self.grouping))
        return DataFrame(self.df.session, L.FlatMapGroupsInPandas(
            shuffled, key_indices, fn, schema))

    def _simple(self, kind, *cols):
        from spark_rapids_tpu.sql import functions as F
        targets = cols or [
            n for n in self.df.columns
            if T.is_numeric(self.df.schema.fields[
                self.df.schema.field_index(n)].dtype)
            and n not in self.names]
        fn = getattr(F, kind)
        return self.agg(*[fn(_col(c)).alias(f"{kind}({c})") for c in targets])

    def sum(self, *cols):
        return self._simple("sum", *cols)

    def min(self, *cols):
        return self._simple("min", *cols)

    def max(self, *cols):
        return self._simple("max", *cols)

    def avg(self, *cols):
        return self._simple("avg", *cols)

    mean = avg


from spark_rapids_tpu.sql.column import col  # noqa: E402,F401  (re-export)
