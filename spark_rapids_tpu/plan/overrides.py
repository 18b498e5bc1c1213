"""The plan-rewrite engine: CPU physical plan → TPU plan with fallback.

[REF: sql-plugin/../GpuOverrides.scala :: GpuOverrides (expressions/execs
 rule maps, wrapPlan), RapidsMeta.scala :: SparkPlanMeta.tagForGpu /
 willNotWorkOnGpu / convertToGpu, GpuTransitionOverrides.scala]

Mechanics mirror the reference faithfully because this IS the product's
soul (SURVEY.md §7):

* every exec/expression class has a rule in a registry;
* each plan node is wrapped in a Meta that accumulates human-readable
  "will not work on TPU because ..." reasons (type checks, per-op conf
  kill-switches, missing rules);
* tagged-ok subtrees convert to Tpu execs; transitions are inserted at
  every boundary (HostToDevice/DeviceToHost — the reference's
  Row/ColumnarToRow analog);
* ``spark.rapids.sql.explain=NOT_ON_TPU|ALL`` reports the rewrite, and
  ``spark.rapids.sql.test.enabled`` turns unexpected fallback into an
  exception (the integration-test mode, SURVEY.md §4.1).
"""

from __future__ import annotations

from typing import Callable, Dict, List, Optional, Type

from spark_rapids_tpu.columnar import dtypes as T
from spark_rapids_tpu.conf import RapidsConf
from spark_rapids_tpu.exec import basic as B
from spark_rapids_tpu.exec.base import CpuExec, ExecNode, TpuExec
from spark_rapids_tpu.exec.transitions import DeviceToHostExec, HostToDeviceExec
from spark_rapids_tpu.ops.expressions import Expression


# ---------------------------------------------------------------------------
# Type support lattice — the TypeSig analog
# [REF: sql-plugin/../TypeChecks.scala :: TypeSig]
# ---------------------------------------------------------------------------

def is_device_supported_type(dt: T.DataType) -> Optional[str]:
    """None if supported on device; else the reason string."""
    if isinstance(dt, T.DecimalType):
        if dt.precision > 38:
            return f"decimal precision {dt.precision} > 38"
        return None
    if isinstance(dt, (T.ArrayType, T.MapType, T.StructType)):
        return f"nested type {dt.simple_name} not yet supported on device"
    if isinstance(dt, (T.BooleanType, T.ByteType, T.ShortType, T.IntegerType,
                       T.LongType, T.FloatType, T.DoubleType, T.StringType,
                       T.BinaryType, T.DateType, T.TimestampType, T.NullType)):
        return None
    return f"type {dt.simple_name} not supported on device"


def is_device_supported_output_type(dt: T.DataType) -> Optional[str]:
    """Exec OUTPUT columns additionally allow array<primitive> — the
    collect_list result (padded element matrix + lengths, D2H-convertible)
    — while expressions over arrays stay unsupported."""
    if isinstance(dt, T.ArrayType):
        et = dt.element_type
        if isinstance(et, (T.ArrayType, T.MapType, T.StructType,
                           T.StringType, T.BinaryType, T.DecimalType)):
            return (f"array element type {et.simple_name} not supported "
                    "on device")
        return None
    return is_device_supported_type(dt)


# ---------------------------------------------------------------------------
# Meta: per-node tagging state
# ---------------------------------------------------------------------------

class ExecMeta:
    def __init__(self, cpu: CpuExec, conf: RapidsConf,
                 children: List["ExecMeta"]):
        self.cpu = cpu
        self.conf = conf
        self.children = children
        self.reasons: List[str] = []
        self.rule: Optional["ExecRule"] = None

    def will_not_work(self, reason: str):
        if reason not in self.reasons:
            self.reasons.append(reason)

    @property
    def can_run_on_tpu(self) -> bool:
        return not self.reasons

    def tag_expressions(self, exprs):
        for e in exprs:
            tag_expression(e, self)

    def tag(self):
        rule = EXEC_RULES.get(type(self.cpu))
        if rule is None:
            self.will_not_work(
                f"no TPU rule for exec {type(self.cpu).__name__}")
            return
        self.rule = rule
        if not self.conf.is_op_enabled("exec", rule.name):
            self.will_not_work(
                f"exec {rule.name} disabled by "
                f"spark.rapids.sql.exec.{rule.name}=false")
        for f in self.cpu.schema.fields:
            r = is_device_supported_output_type(f.dtype)
            if r:
                self.will_not_work(f"output column '{f.name}': {r}")
        # a CPU-falling child feeds this node through an H2D transition —
        # every column of the child's schema must survive the transfer
        # [REF: GpuTransitionOverrides.scala — transition type validation]
        for c in self.children:
            if not c.can_run_on_tpu:
                for f in c.cpu.schema.fields:
                    r = is_device_supported_output_type(f.dtype)
                    if r:
                        self.will_not_work(
                            f"input column '{f.name}' cannot cross the "
                            f"host→device transition: {r}")
        rule.tag(self)


def tag_expression(e: Expression, meta: ExecMeta):
    from spark_rapids_tpu import conf as C
    name = type(e).__name__
    if not meta.conf.is_op_enabled("expression", name):
        meta.will_not_work(
            f"expression {name} disabled by "
            f"spark.rapids.sql.expression.{name}=false")
    incompat = getattr(type(e), "incompat", None)
    if incompat and not meta.conf.get(C.INCOMPATIBLE_OPS):
        meta.will_not_work(
            f"expression {name} is not fully compatible with Spark "
            f"({incompat}); set "
            "spark.rapids.sql.incompatibleOps.enabled=true to enable")
    if meta.conf.ansi_enabled and getattr(type(e), "ansi_sensitive", False):
        meta.will_not_work(
            f"expression {name} under spark.sql.ansi.enabled=true: device "
            "lowering implements non-ANSI semantics (overflow wraps, "
            "invalid input nulls) — CPU fallback until ANSI kernels exist")
    hook = getattr(e, "device_support_reason", None)
    if hook is not None:
        r = hook(meta.conf)
        if r:
            meta.will_not_work(f"expression {name}: {r}")
    from spark_rapids_tpu.ops.expressions import BoundReference, sig_tag
    if isinstance(e, BoundReference):
        # direct column pass-through supports everything a batch can
        # carry (incl. array<numeric>); computed expressions stay
        # restricted to scalar device types
        r = is_device_supported_output_type(e.dtype)
    else:
        r = is_device_supported_type(e.dtype)
    if r:
        meta.will_not_work(f"expression {e}: {r}")
    # per-op TypeSig [REF: TypeChecks.scala]: the class declares which
    # type tags its device lowering produces/accepts — checked here,
    # rendered as the support matrix in docs/supported_ops.md
    cls = type(e)
    tag = sig_tag(e.dtype)
    if tag not in cls.type_sig:
        meta.will_not_work(
            f"expression {name} does not produce {tag} on device "
            f"(type sig: {', '.join(sorted(cls.type_sig))})")
    in_sig = cls.input_sig if cls.input_sig is not None else cls.type_sig
    for c in e.children:
        ctag = sig_tag(c.dtype)
        if ctag not in in_sig:
            meta.will_not_work(
                f"expression {name} does not accept a {ctag} input on "
                f"device (input sig: {', '.join(sorted(in_sig))})")
    if not hasattr(e, "eval_tpu") or (
            type(e).eval_tpu is Expression.eval_tpu):
        meta.will_not_work(f"expression {name} has no TPU implementation")
    for c in e.children:
        tag_expression(c, meta)


# ---------------------------------------------------------------------------
# Rules
# ---------------------------------------------------------------------------

class ExecRule:
    """One entry of the GpuOverrides execs map.

    ``convert(cpu, tpu_children, conf)`` — conf lets conversions pick
    distributed variants (e.g. ICI shuffle mode splits aggregates)."""

    def __init__(self, name: str,
                 tag: Callable[[ExecMeta], None],
                 convert: Callable[[CpuExec, List[TpuExec], "RapidsConf"],
                                   TpuExec],
                 desc: str = ""):
        self.name = name
        self._tag = tag
        self.convert = convert
        self.desc = desc

    def tag(self, meta: ExecMeta):
        self._tag(meta)


EXEC_RULES: Dict[Type[CpuExec], ExecRule] = {}


def register_exec(cpu_cls: Type[CpuExec], name: str, desc: str = ""):
    def deco(fns):
        tag, convert = fns
        EXEC_RULES[cpu_cls] = ExecRule(name, tag, convert, desc)
        return fns
    return deco


def _tag_scan(meta: ExecMeta):
    pass


def _convert_scan(cpu: B.CpuScanExec, children, conf):
    from spark_rapids_tpu.parallel.executor import get_executor
    ctx = get_executor()
    executor = ((ctx.process_id, ctx.num_processes) if ctx is not None
                else (0, 1))
    return B.TpuScanExec(cpu.table, cpu.schema, cpu.num_partitions(),
                         cpu.batch_rows, executor=executor)


EXEC_RULES[B.CpuScanExec] = ExecRule(
    "InMemoryScan", _tag_scan, _convert_scan,
    "in-memory table scan landing device-resident columnar batches")

EXEC_RULES[B.CpuProjectExec] = ExecRule(
    "Project",
    lambda m: m.tag_expressions(m.cpu.exprs),
    lambda cpu, ch, conf: B.TpuProjectExec(cpu.exprs, cpu.schema, ch[0]),
    "columnar projection")

EXEC_RULES[B.CpuFilterExec] = ExecRule(
    "Filter",
    lambda m: m.tag_expressions([m.cpu.condition]),
    lambda cpu, ch, conf: B.TpuFilterExec(cpu.condition, ch[0]),
    "columnar filter (predicate folds into the selection mask)")

EXEC_RULES[B.CpuLocalLimitExec] = ExecRule(
    "LocalLimit",
    lambda m: None,
    lambda cpu, ch, conf: B.TpuLocalLimitExec(cpu.n, ch[0]),
    "limit over live rows")

EXEC_RULES[B.CpuGlobalLimitExec] = ExecRule(
    "GlobalLimit",
    lambda m: None,
    lambda cpu, ch, conf: B.TpuGlobalLimitExec(cpu.n, ch[0]),
    "global limit cut across partitions")

EXEC_RULES[B.CpuUnionExec] = ExecRule(
    "Union",
    lambda m: None,
    lambda cpu, ch, conf: B.TpuUnionExec(ch),
    "union of children partitions")


def _tag_aggregate(meta: ExecMeta):
    from spark_rapids_tpu.exec.aggregate import CpuAggregateExec
    from spark_rapids_tpu.ops.aggregates import (
        Average, CollectList, Count, CountStar, First, Max, Min,
        Percentile, Sum, _VarianceBase)
    cpu: CpuAggregateExec = meta.cpu
    meta.tag_expressions(cpu.grouping)
    for fn in cpu.fns:
        if isinstance(fn, Sum) and meta.conf.ansi_enabled:
            meta.will_not_work(
                "sum under spark.sql.ansi.enabled=true: device sum wraps "
                "on overflow (non-ANSI) — CPU fallback")
        if not isinstance(fn, (Sum, Min, Max, Count, CountStar, Average,
                               First, _VarianceBase, CollectList,
                               Percentile)):
            meta.will_not_work(
                f"aggregate function {fn.name} has no TPU implementation")
            continue
        if not isinstance(fn, CountStar):
            meta.tag_expressions([fn.child])
        if isinstance(fn, _VarianceBase) and not T.is_numeric(
                fn.input_dtype):
            meta.will_not_work(f"{fn.name} needs a numeric input")
        if isinstance(fn, CollectList):
            if isinstance(fn.input_dtype,
                          (T.StringType, T.BinaryType, T.DecimalType,
                           T.ArrayType)):
                meta.will_not_work(
                    f"collect_list over {fn.input_dtype.simple_name} not "
                    "on device yet (element matrix is numeric-only)")


def _convert_aggregate(cpu, ch, conf):
    from spark_rapids_tpu import conf as C
    from spark_rapids_tpu.exec.aggregate import TpuHashAggregateExec
    from spark_rapids_tpu.exec.distributed import ici_active
    from spark_rapids_tpu.ops.aggregates import CollectList, Percentile
    has_nans = bool(conf.get(C.HAS_NANS))
    tuning = dict(has_nans=has_nans,
                  bucket_rows=conf.get(C.AGG_BUCKET_ROWS),
                  skip_ratio=conf.get(C.AGG_SKIP_RATIO))
    from spark_rapids_tpu.exec.aggregate import is_holistic_fn
    # holistic functions (collect/percentile, and min/max/first over
    # multi-limb dtypes) run the single-kernel gathered path — they
    # cannot ride buffer batches through a partial/final split
    has_collect = any(is_holistic_fn(f) for f in cpu.fns)
    if ici_active(conf) and cpu.grouping and not has_collect:
        # distributed: {partial agg → hash exchange on keys → final agg}
        # — one SPMD all_to_all per shuffle stage (SURVEY §5.8)
        from spark_rapids_tpu.exec.distributed import TpuIciShuffleExchangeExec
        from spark_rapids_tpu.ops.expressions import BoundReference
        partial = TpuHashAggregateExec(cpu.grouping, cpu.fns, None, ch[0],
                                       mode="partial", **tuning)
        partial.schema = partial._buffer_schema()
        keys = [BoundReference(i, g.dtype)
                for i, g in enumerate(cpu.grouping)]
        exchange = TpuIciShuffleExchangeExec(partial, keys)
        return TpuHashAggregateExec(cpu.grouping, cpu.fns, cpu.schema,
                                    exchange, mode="final", **tuning)
    return TpuHashAggregateExec(cpu.grouping, cpu.fns, cpu.schema, ch[0],
                                **tuning)


def _register_lazy_rules():
    """Rules for exec classes defined in lazily-imported modules."""
    from spark_rapids_tpu.exec.aggregate import CpuAggregateExec
    EXEC_RULES.setdefault(CpuAggregateExec, ExecRule(
        "HashAggregate", _tag_aggregate, _convert_aggregate,
        "sort-based device groupby (lax.sort + segment reduce)"))
    try:
        from spark_rapids_tpu.exec.sort import CpuSortExec
        from spark_rapids_tpu.exec.sort import _tag_sort, _convert_sort
        EXEC_RULES.setdefault(CpuSortExec, ExecRule(
            "Sort", _tag_sort, _convert_sort,
            "device lexicographic sort (lax.sort on orderable keys)"))
    except ImportError:
        pass
    try:
        from spark_rapids_tpu.exec.join import (
            CpuJoinExec, _tag_join, _convert_join)
        EXEC_RULES.setdefault(CpuJoinExec, ExecRule(
            "SortMergeJoin", _tag_join, _convert_join,
            "device sort-merge equi-join"))
    except ImportError:
        pass
    try:
        from spark_rapids_tpu.exec.window import (
            CpuWindowExec, _tag_window, _convert_window)
        EXEC_RULES.setdefault(CpuWindowExec, ExecRule(
            "Window", _tag_window, _convert_window,
            "device window functions (sorted segmented scans)"))
    except ImportError:
        pass
    try:
        from spark_rapids_tpu.exec.exchange import (
            CpuShuffleExchangeExec, _tag_exchange, _convert_exchange)
        EXEC_RULES.setdefault(CpuShuffleExchangeExec, ExecRule(
            "ShuffleExchange", _tag_exchange, _convert_exchange,
            "device hash partitioning (bit-exact Spark murmur3)"))
    except ImportError:
        pass
    try:
        from spark_rapids_tpu.io.parquet import (
            CpuParquetScanExec, _tag_parquet, _convert_parquet)
        EXEC_RULES.setdefault(CpuParquetScanExec, ExecRule(
            "ParquetScan", _tag_parquet, _convert_parquet,
            "parquet scan landing device-resident batches"))
    except ImportError:
        pass
    try:
        from spark_rapids_tpu.exec import misc as M
        EXEC_RULES.setdefault(M.CpuRangeExec, ExecRule(
            "Range", M._tag_range, M._convert_range,
            "device iota id generation (no host data)"))
        EXEC_RULES.setdefault(M.CpuSampleExec, ExecRule(
            "Sample", M._tag_sample, M._convert_sample,
            "hash-Bernoulli sample folded into the sel mask"))
        EXEC_RULES.setdefault(M.CpuExpandExec, ExecRule(
            "Expand", M._tag_expand, M._convert_expand,
            "grouping-sets expansion (one kernel per projection)"))
        EXEC_RULES.setdefault(M.CpuGenerateExec, ExecRule(
            "Generate", M._tag_generate, M._convert_generate,
            "explode/posexplode via element-matrix reshape"))
        EXEC_RULES.setdefault(M.CpuTopNExec, ExecRule(
            "TakeOrderedAndProject", M._tag_topn, M._convert_topn,
            "per-partition device topN + winner merge"))
    except ImportError:
        pass
    try:
        from spark_rapids_tpu.exec import python_udf as PU
        EXEC_RULES.setdefault(PU.CpuArrowEvalPythonExec, ExecRule(
            "ArrowEvalPython", PU._tag_python_eval,
            PU._convert_python_eval,
            "python/pandas UDFs: device args → in-process arrow bridge"))
        EXEC_RULES.setdefault(PU.CpuMapInPandasExec, ExecRule(
            "MapInPandas", PU._tag_map_in_pandas,
            PU._convert_map_in_pandas,
            "mapInPandas over the arrow bridge"))
        EXEC_RULES.setdefault(PU.CpuFlatMapGroupsInPandasExec, ExecRule(
            "FlatMapGroupsInPandas", PU._tag_flat_map_groups,
            PU._convert_flat_map_groups,
            "grouped-map pandas UDF above a device hash exchange"))
    except ImportError:
        pass


# ---------------------------------------------------------------------------
# The rewrite pass
# ---------------------------------------------------------------------------

class OverrideResult:
    def __init__(self, plan: ExecNode, metas: List[ExecMeta]):
        self.plan = plan
        self.metas = metas

    def fallback_report(self) -> List[str]:
        out = []
        for m in self.metas:
            if not m.can_run_on_tpu:
                for r in m.reasons:
                    out.append(
                        f"!Exec <{type(m.cpu).__name__}> cannot run on TPU "
                        f"because {r}")
        return out

    def fallback_summary(self) -> dict:
        """The fallback BUDGET as a metric [REF: ExplainPlanImpl — the
        reference's explain=NOT_ON_GPU output, condensed to the number
        that tracks progress]: how many plan operators run on device vs
        fell back, with reasons."""
        device = sum(1 for m in self.metas if m.can_run_on_tpu)
        fallen = [m for m in self.metas if not m.can_run_on_tpu]
        return {
            "device_ops": device,
            "fallback_ops": len(fallen),
            "device_fraction": round(
                device / max(len(self.metas), 1), 3),
            "fallback_reasons": sorted(
                {f"{type(m.cpu).__name__}: {r}"
                 for m in fallen for r in m.reasons}),
        }


def wrap(cpu: CpuExec, conf: RapidsConf, all_metas: List[ExecMeta]) -> ExecMeta:
    children = [wrap(c, conf, all_metas) for c in cpu.children
                if isinstance(c, CpuExec)]
    meta = ExecMeta(cpu, conf, children)
    meta.tag()
    all_metas.append(meta)
    return meta


def _rebuild_cpu(cpu: CpuExec, new_children: List[ExecNode]) -> CpuExec:
    """Copy a CPU exec onto (possibly transition-wrapped) children.

    A shallow copy, NOT in-place mutation: the original plan nodes stay
    pristine so re-planning/re-executing a DataFrame never sees a
    half-rewritten tree."""
    import copy
    clone = copy.copy(cpu)
    clone._children = tuple(new_children)
    clone.metrics = {k: type(m)(m.name) for k, m in cpu.metrics.items()}
    return clone


def convert_meta(meta: ExecMeta) -> ExecNode:
    """Bottom-up conversion with transition insertion."""
    converted = [convert_meta(c) for c in meta.children]
    if meta.can_run_on_tpu:
        tpu_children = [
            c if isinstance(c, TpuExec) else HostToDeviceExec(c)
            for c in converted
        ]
        return meta.rule.convert(meta.cpu, tpu_children, meta.conf)
    cpu_children = [
        c if isinstance(c, CpuExec) else DeviceToHostExec(c)
        for c in converted
    ]
    return _rebuild_cpu(meta.cpu, cpu_children)


def _estimated_row_bytes(schema: T.StructType,
                         str_width: Optional[int] = None) -> int:
    """Rough bytes/row for batch-size targeting and working-set
    accounting.  ``str_width``: known string-matrix width (the ICI
    exchange passes it); default is a 40-byte planning-time guess."""
    total = 0
    for f in schema.fields:
        if isinstance(f.dtype, (T.StringType, T.BinaryType)):
            total += (max(str_width, 8) + 4) if str_width is not None \
                else 40
        else:
            total += 8
        total += 1  # validity
    return max(total, 1)


def insert_coalesce(node: ExecNode, conf: RapidsConf) -> ExecNode:
    """The GpuTransitionOverrides coalesce pass [REF:
    GpuTransitionOverrides.scala + GpuCoalesceBatches.scala]:

    * a TargetSize coalesce above every H2D transition (CPU-fallback
      sources emit small batches; merge them up to
      ``spark.rapids.sql.batchSizeBytes`` before device operators), and
    * a RequireSingleBatch coalesce under whole-partition consumers
      (sort / join / window), making the batching contract a plan node
      instead of ad-hoc concatenation inside the operator.
    """
    from spark_rapids_tpu.exec.basic import TpuCoalesceBatchesExec
    from spark_rapids_tpu.exec.distributed import TpuIciShuffleExchangeExec
    from spark_rapids_tpu.exec.join import TpuBroadcastExchangeExec
    from spark_rapids_tpu.exec.sort import TpuSortExec
    from spark_rapids_tpu.exec.window import TpuWindowExec
    from spark_rapids_tpu import conf as C

    node._children = tuple(insert_coalesce(c, conf)
                           for c in node.children)
    if isinstance(node, HostToDeviceExec):
        target = max(conf.get(C.BATCH_SIZE_BYTES)
                     // _estimated_row_bytes(node.schema),
                     conf.min_bucket_rows)
        # row-capped at batchRows: static-shape kernels compile per
        # pow-2 bucket, and batchRows is THE documented bound on bucket
        # size — an unbounded byte target (512 MB / 8-byte rows = a
        # 64M-row bucket) must never override it
        target = min(target, conf.batch_rows)
        return TpuCoalesceBatchesExec(node, target_rows=target)
    if isinstance(node, (TpuSortExec, TpuWindowExec)):
        # RequireSingleBatch is only made plan-visible for single-
        # partition children: there it replaces the operator's internal
        # concat 1:1.  Multi-partition children keep the operator's own
        # cross-partition gather (one concat) — a per-partition coalesce
        # below it would copy every row twice.  JOINS are deliberately
        # NOT here (round-5 fix): a pre-concatenated whole side would
        # bypass TpuSortMergeJoin's row-capped sub-partitioning — the
        # single giant gather (6M rows → one 8M bucket on TPC-H q10)
        # is exactly what killed the r4 TPU worker.
        node._children = tuple(
            TpuCoalesceBatchesExec(c, require_single=True)
            if isinstance(c, TpuExec) and c.num_partitions() == 1
            and not isinstance(
                c, (TpuCoalesceBatchesExec, TpuIciShuffleExchangeExec,
                    TpuBroadcastExchangeExec))
            else c
            for c in node._children)
    return node


# multi-executor mode supports the partition-preserving pipeline around
# ICI exchanges; global-gather operators would silently compute on one
# process's slice only, so they fail loudly instead.  The name list
# covers operators wrong-by-semantics even when partition-preserving
# (windows need co-partitioning; broadcast captures one slice; the
# non-collective shuffle exchanges are in-process only); the structural
# checks below catch every gather point and partition-structure change,
# including CPU-fallback nodes.
# Sort/Window/TopN/GlobalLimit are distributable since round 5: Sort
# rides a RANGE exchange + per-partition local sorts, Window a hash
# exchange on partition_by, TopN/GlobalLimit reduce locally then
# rendezvous-allgather their (tiny) winner rows / counts.
_MULTIPROC_UNSUPPORTED = {
    "TpuBroadcastExchangeExec", "TpuExpandExec",
    "TpuGenerateExec", "TpuPythonUDFExec", "TpuSampleExec",
    "CpuSortExec", "CpuGlobalLimitExec", "CpuTakeOrderedAndProjectExec",
    "CpuWindowExec", "CpuSampleExec", "CpuPythonUDFExec",
    "TpuShuffleExchangeExec", "CpuShuffleExchangeExec",
}


def _validate_multiproc(plan) -> None:
    from spark_rapids_tpu.exec.distributed import TpuIciShuffleExchangeExec
    from spark_rapids_tpu.exec.join import CpuJoinExec, TpuSortMergeJoinExec

    def bad(name, why):
        raise NotImplementedError(
            f"{name} is not supported in multi-executor mode "
            f"(executor.count > 1): {why}. Run on a single executor, or "
            "restructure the query around hash exchanges (agg / "
            "co-partitioned equi-join pipelines are supported).")

    def has_exchange(node):
        return isinstance(node, TpuIciShuffleExchangeExec) or any(
            has_exchange(c) for c in node.children)

    def walk(node):
        name = type(node).__name__
        if name in _MULTIPROC_UNSUPPORTED:
            bad(name, "it computes on one executor's slice only")
        if isinstance(node, TpuSortMergeJoinExec) and not node.partitioned:
            bad(name, "only co-partitioned (ICI-exchanged) equi-joins "
                "are distributed; this join would match one slice "
                "against another")
        if isinstance(node, CpuJoinExec):
            bad(name, "CPU-fallback joins gather one slice per process")
        gather_ok = getattr(node, "_multiproc_gather_ok", False)
        for c in node.children:
            # structural guards (catch CPU fallbacks and any operator
            # missed by name): a gather point collapses partitions this
            # process only partly owns; a partition-structure change
            # above an exchange breaks local-partition ownership.
            # Nodes flagged _multiproc_gather_ok (TopN, GlobalLimit)
            # gather via an explicit cross-process allgather instead.
            if (not isinstance(node, TpuIciShuffleExchangeExec)
                    and not gather_ok
                    and c.num_partitions() > 1
                    and node.num_partitions() == 1):
                bad(name, "it gathers all partitions into one, but "
                    "each executor holds only its slice")
            if (has_exchange(c) and not isinstance(
                    node, TpuIciShuffleExchangeExec)
                    and not gather_ok
                    and node.num_partitions() != c.num_partitions()):
                bad(name, "it re-groups partitions above a collective "
                    "exchange, breaking local-partition ownership")
            walk(c)

    walk(plan)


def apply_overrides(cpu_plan: CpuExec, conf: RapidsConf) -> OverrideResult:
    """GpuOverrides.apply + GpuTransitionOverrides in one pass."""
    if not conf.sql_enabled:
        return OverrideResult(cpu_plan, [])
    # configure the HBM budget arbiter from this query's conf (memory
    # keys + OOM fault injection) before any device materialization
    from spark_rapids_tpu.runtime.memory import get_manager
    get_manager(conf)
    from spark_rapids_tpu.runtime.resilience import configure_from_conf
    configure_from_conf(conf)
    _register_lazy_rules()
    metas: List[ExecMeta] = []
    root = wrap(cpu_plan, conf, metas)
    plan = convert_meta(root)
    if isinstance(plan, TpuExec):
        plan = DeviceToHostExec(plan)
    plan = insert_coalesce(plan, conf)
    # whole-stage fusion last: it needs the final converted tree (so the
    # member signatures it records match what an unfused run of this
    # exact plan would execute — see fusion/regions.py)
    from spark_rapids_tpu.fusion import fuse_plan
    plan, _ = fuse_plan(plan, conf)
    from spark_rapids_tpu.parallel.executor import get_executor
    if get_executor() is not None:
        _validate_multiproc(plan)
    from spark_rapids_tpu import conf as C
    lore_tag = str(conf.get(C.LORE_TAG)).strip()
    if lore_tag:
        from spark_rapids_tpu.utils.lore import install_lore_taps
        plan = install_lore_taps(plan, lore_tag,
                                 str(conf.get(C.LORE_DUMP_PATH)))
    result = OverrideResult(plan, metas)

    explain = conf.explain
    report = result.fallback_report()
    if explain == "ALL" or (explain in ("NOT_ON_GPU", "NOT_ON_TPU")
                            and report):
        print("TPU plan rewrite:")
        for line in report:
            print("  " + line)
        if explain == "ALL":
            print(plan.tree_string())

    if conf.test_enabled and report:
        allowed = set(conf.allowed_non_gpu)
        bad = [m for m in metas if not m.can_run_on_tpu
               and type(m.cpu).__name__ not in allowed
               and (EXEC_RULES.get(type(m.cpu)) is None
                    or EXEC_RULES[type(m.cpu)].name not in allowed)]
        if bad:
            lines = "\n".join(r for m in bad for r in m.reasons)
            raise AssertionError(
                "Part of the plan is not columnar (TPU test mode): \n"
                + lines)
    return result
