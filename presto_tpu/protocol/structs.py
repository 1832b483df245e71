"""Coordinator-protocol structs: Jackson-compatible dataclasses + JSON codec.

The contract the Java coordinator speaks to every worker implementation
(reference: presto-main-base/.../server/TaskUpdateRequest.java:37,
sql/planner/PlanFragment.java:52, spi/relation/RowExpression.java
@JsonSubTypes, spi/plan/* PlanNode @JsonTypeInfo(MINIMAL_CLASS, "@type")).
The C++ worker generates these structs from the Java sources
(presto_cpp/presto_protocol/java-to-struct-json.py); here the same wire
shape is expressed as a declarative `_SCHEMA` per dataclass driving one
generic encoder/decoder — field names and "@type" discriminators follow
the Java @JsonProperty/@JsonSubTypes annotations exactly, verified against
the captured coordinator JSON in the reference's protocol test data.

Unknown/connector-specific payloads (TableHandle, ColumnHandle, splits,
FunctionHandle) are carried as raw JSON — the worker interprets only the
parts it executes, like PrestoToVeloxQueryPlan does.
"""

from __future__ import annotations

import base64
import dataclasses
import json
from typing import Any, Dict, List, Optional, Tuple

# ---------------------------------------------------------------------------
# Generic schema-driven codec
# ---------------------------------------------------------------------------
# Codec forms in _SCHEMA entries (pyname, jsonname, codec):
#   None                 raw JSON value
#   a struct class       nested struct
#   ("list", c)          list of codec c
#   ("listlist", c)      list of list of codec c
#   ("opt", c)           Optional (absent/None <-> None); Jackson NON_ABSENT
#   ("map", c)           dict with string keys, values of codec c


def _enc(codec, v):
    if v is None:
        return None
    if codec is None:
        return v
    if isinstance(codec, tuple):
        kind = codec[0]
        if kind == "list":
            return [_enc(codec[1], x) for x in v]
        if kind == "listlist":
            return [[_enc(codec[1], x) for x in row] for row in v]
        if kind == "opt":
            return _enc(codec[1], v)
        if kind == "map":
            return {k: _enc(codec[1], x) for k, x in v.items()}
        raise ValueError(kind)
    return codec.to_json(v)


def _dec(codec, j):
    if j is None:
        return None
    if codec is None:
        return j
    if isinstance(codec, tuple):
        kind = codec[0]
        if kind == "list":
            return [_dec(codec[1], x) for x in j]
        if kind == "listlist":
            return [[_dec(codec[1], x) for x in row] for row in j]
        if kind == "opt":
            return _dec(codec[1], j)
        if kind == "map":
            return {k: _dec(codec[1], x) for k, x in j.items()}
        raise ValueError(kind)
    return codec.from_json(j)


class Struct:
    _SCHEMA: List[Tuple[str, str, Any]] = []
    _TYPE_KEY: Optional[str] = None      # "@type" discriminator value

    @classmethod
    def to_json(cls, self) -> Dict[str, Any]:
        out: Dict[str, Any] = {}
        if self._TYPE_KEY is not None:
            out["@type"] = self._TYPE_KEY
        for py, js, codec in self._SCHEMA:
            v = getattr(self, py)
            if v is None and isinstance(codec, tuple) and codec[0] == "opt":
                continue                 # Jackson NON_ABSENT optionals
            out[js] = _enc(codec, v)
        return out

    @classmethod
    def from_json(cls, j: Dict[str, Any]):
        kwargs = {}
        for py, js, codec in cls._SCHEMA:
            kwargs[py] = _dec(codec, j.get(js))
        return cls(**kwargs)

    def dumps(self) -> str:
        # Insertion order is semantic for Assignments/aggregations maps
        # (Jackson serializes LinkedHashMap in order; translate resolves
        # output layouts positionally from it) — never sort_keys here.
        return json.dumps(type(self).to_json(self))

    @classmethod
    def loads(cls, s: str):
        return cls.from_json(json.loads(s))


class Polymorphic(Struct):
    """Base with an "@type"-dispatched registry (Jackson JsonTypeInfo)."""
    _REGISTRY: Dict[str, type] = {}

    @classmethod
    def register(cls, type_key: str):
        def deco(sub):
            sub._TYPE_KEY = type_key
            cls._REGISTRY[type_key] = sub
            return sub
        return deco

    @classmethod
    def to_json(cls, self):
        if isinstance(self, RawNode):
            return RawNode.to_json(self)
        return Struct.to_json.__func__(type(self), self)

    @classmethod
    def from_json(cls, j):
        key = j.get("@type")
        sub = cls._REGISTRY.get(key)
        if sub is None:
            return RawNode(type_key=key, payload=dict(j))
        return Struct.from_json.__func__(sub, j)


@dataclasses.dataclass
class RawNode:
    """Unknown polymorphic payload, preserved verbatim for round-trips."""
    type_key: Optional[str]
    payload: Dict[str, Any]

    _TYPE_KEY = None

    @classmethod
    def to_json(cls, self):
        return dict(self.payload)

    @classmethod
    def from_json(cls, j):
        return cls(j.get("@type"), dict(j))


# ---------------------------------------------------------------------------
# RowExpression hierarchy (spi/relation, @JsonSubTypes names)
# ---------------------------------------------------------------------------

class RowExpr(Polymorphic):
    _REGISTRY: Dict[str, type] = {}


@RowExpr.register("variable")
@dataclasses.dataclass
class Variable(RowExpr):
    name: str = ""
    type: str = ""
    _SCHEMA = [("name", "name", None), ("type", "type", None)]


@RowExpr.register("call")
@dataclasses.dataclass
class Call(RowExpr):
    displayName: str = ""
    functionHandle: Any = None           # raw: $static signature etc.
    returnType: str = ""
    arguments: List[Any] = dataclasses.field(default_factory=list)
    _SCHEMA = [
        ("displayName", "displayName", None),
        ("functionHandle", "functionHandle", None),
        ("returnType", "returnType", None),
        ("arguments", "arguments", ("list", RowExpr)),
    ]


@RowExpr.register("constant")
@dataclasses.dataclass
class Constant(RowExpr):
    valueBlock: str = ""                 # base64 SerializedPage block
    type: str = ""
    _SCHEMA = [("valueBlock", "valueBlock", None), ("type", "type", None)]


@RowExpr.register("special")
@dataclasses.dataclass
class SpecialForm(RowExpr):
    form: str = ""
    returnType: str = ""
    arguments: List[Any] = dataclasses.field(default_factory=list)
    _SCHEMA = [
        ("form", "form", None),
        ("returnType", "returnType", None),
        ("arguments", "arguments", ("list", RowExpr)),
    ]


@RowExpr.register("input")
@dataclasses.dataclass
class InputReference(RowExpr):
    field: int = 0
    type: str = ""
    _SCHEMA = [("field", "field", None), ("type", "type", None)]


@RowExpr.register("lambda")
@dataclasses.dataclass
class Lambda(RowExpr):
    argumentTypes: List[Any] = dataclasses.field(default_factory=list)
    arguments: List[str] = dataclasses.field(default_factory=list)
    body: Any = None
    _SCHEMA = [
        ("argumentTypes", "argumentTypes", None),
        ("arguments", "arguments", None),
        ("body", "body", RowExpr),
    ]


# ---------------------------------------------------------------------------
# Ordering / partitioning schemes
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class Ordering(Struct):
    variable: Variable = None
    sortOrder: str = "ASC_NULLS_LAST"
    _SCHEMA = [("variable", "variable", Variable),
               ("sortOrder", "sortOrder", None)]


@dataclasses.dataclass
class OrderingScheme(Struct):
    orderBy: List[Ordering] = dataclasses.field(default_factory=list)
    _SCHEMA = [("orderBy", "orderBy", ("list", Ordering))]


@dataclasses.dataclass
class PartitioningHandle(Struct):
    connectorId: Any = None
    transactionHandle: Any = None
    connectorHandle: Any = None          # raw: $remote system handle
    _SCHEMA = [
        ("connectorId", "connectorId", ("opt", None)),
        ("transactionHandle", "transactionHandle", ("opt", None)),
        ("connectorHandle", "connectorHandle", None),
    ]


@dataclasses.dataclass
class PartitioningScheme_Partitioning(Struct):
    handle: PartitioningHandle = None
    arguments: List[Any] = dataclasses.field(default_factory=list)
    _SCHEMA = [("handle", "handle", PartitioningHandle),
               ("arguments", "arguments", ("list", RowExpr))]


@dataclasses.dataclass
class PartitioningScheme(Struct):
    partitioning: PartitioningScheme_Partitioning = None
    outputLayout: List[Variable] = dataclasses.field(default_factory=list)
    hashColumn: Optional[Variable] = None
    replicateNullsAndAny: bool = False
    scaleWriters: bool = False
    encoding: str = "COLUMNAR"
    bucketToPartition: Any = None
    _SCHEMA = [
        ("partitioning", "partitioning", PartitioningScheme_Partitioning),
        ("outputLayout", "outputLayout", ("list", Variable)),
        ("hashColumn", "hashColumn", ("opt", Variable)),
        ("replicateNullsAndAny", "replicateNullsAndAny", None),
        ("scaleWriters", "scaleWriters", None),
        ("encoding", "encoding", None),
        ("bucketToPartition", "bucketToPartition", ("opt", None)),
    ]


# ---------------------------------------------------------------------------
# PlanNode hierarchy (@JsonTypeInfo MINIMAL_CLASS => ".XxxNode" keys for
# spi/plan, fully-qualified names for engine-internal nodes)
# ---------------------------------------------------------------------------

class PlanNode(Polymorphic):
    _REGISTRY: Dict[str, type] = {}


@PlanNode.register(".OutputNode")
@dataclasses.dataclass
class OutputNode(PlanNode):
    id: str = ""
    source: Any = None
    columnNames: List[str] = dataclasses.field(default_factory=list)
    outputVariables: List[Variable] = dataclasses.field(default_factory=list)
    _SCHEMA = [
        ("id", "id", None),
        ("source", "source", PlanNode),
        ("columnNames", "columnNames", None),
        ("outputVariables", "outputVariables", ("list", Variable)),
    ]


@PlanNode.register(".TableScanNode")
@dataclasses.dataclass
class TableScanNode(PlanNode):
    id: str = ""
    table: Any = None                    # raw TableHandle (connector)
    outputVariables: List[Variable] = dataclasses.field(default_factory=list)
    assignments: Dict[str, Any] = dataclasses.field(default_factory=dict)
    _SCHEMA = [
        ("id", "id", None),
        ("table", "table", None),
        ("outputVariables", "outputVariables", ("list", Variable)),
        ("assignments", "assignments", None),
    ]


@PlanNode.register(".FilterNode")
@dataclasses.dataclass
class FilterNode(PlanNode):
    id: str = ""
    source: Any = None
    predicate: Any = None
    _SCHEMA = [
        ("id", "id", None),
        ("source", "source", PlanNode),
        ("predicate", "predicate", RowExpr),
    ]


@dataclasses.dataclass
class Assignments(Struct):
    """Map "name<type>" -> RowExpression (spi/plan/Assignments.java wraps
    the map under its own "assignments" property)."""
    assignments: Dict[str, Any] = dataclasses.field(default_factory=dict)
    _SCHEMA = [("assignments", "assignments", ("map", RowExpr))]


@PlanNode.register(".ProjectNode")
@dataclasses.dataclass
class ProjectNode(PlanNode):
    id: str = ""
    source: Any = None
    assignments: Assignments = None
    locality: str = "LOCAL"
    _SCHEMA = [
        ("id", "id", None),
        ("source", "source", PlanNode),
        ("assignments", "assignments", Assignments),
        ("locality", "locality", None),
    ]


@dataclasses.dataclass
class Aggregation(Struct):
    call: Call = None
    filter: Optional[Any] = None
    orderBy: Optional[OrderingScheme] = None
    distinct: bool = False
    mask: Optional[Variable] = None
    # legacy duplicates the coordinator also emits alongside `call`
    functionHandle: Any = None
    arguments: Optional[List[Any]] = None
    _SCHEMA = [
        ("call", "call", Call),
        ("filter", "filter", ("opt", RowExpr)),
        ("orderBy", "orderBy", ("opt", OrderingScheme)),
        ("distinct", "distinct", None),
        ("mask", "mask", ("opt", Variable)),
        ("functionHandle", "functionHandle", ("opt", None)),
        ("arguments", "arguments", ("opt", ("list", RowExpr))),
    ]


@dataclasses.dataclass
class GroupingSetDescriptor(Struct):
    groupingKeys: List[Variable] = dataclasses.field(default_factory=list)
    groupingSetCount: int = 1
    globalGroupingSets: List[int] = dataclasses.field(default_factory=list)
    _SCHEMA = [
        ("groupingKeys", "groupingKeys", ("list", Variable)),
        ("groupingSetCount", "groupingSetCount", None),
        ("globalGroupingSets", "globalGroupingSets", None),
    ]


@PlanNode.register(".AggregationNode")
@dataclasses.dataclass
class AggregationNode(PlanNode):
    id: str = ""
    source: Any = None
    aggregations: Dict[str, Aggregation] = dataclasses.field(
        default_factory=dict)
    groupingSets: GroupingSetDescriptor = None
    preGroupedVariables: List[Variable] = dataclasses.field(
        default_factory=list)
    step: str = "SINGLE"
    hashVariable: Optional[Variable] = None
    groupIdVariable: Optional[Variable] = None
    aggregationId: Optional[int] = None
    _SCHEMA = [
        ("id", "id", None),
        ("source", "source", PlanNode),
        ("aggregations", "aggregations", ("map", Aggregation)),
        ("groupingSets", "groupingSets", GroupingSetDescriptor),
        ("preGroupedVariables", "preGroupedVariables", ("list", Variable)),
        ("step", "step", None),
        ("hashVariable", "hashVariable", ("opt", Variable)),
        ("groupIdVariable", "groupIdVariable", ("opt", Variable)),
        ("aggregationId", "aggregationId", ("opt", None)),
    ]


@dataclasses.dataclass
class EquiJoinClause(Struct):
    left: Variable = None
    right: Variable = None
    _SCHEMA = [("left", "left", Variable), ("right", "right", Variable)]


@PlanNode.register(".JoinNode")
@dataclasses.dataclass
class JoinNode(PlanNode):
    id: str = ""
    type: str = "INNER"
    left: Any = None
    right: Any = None
    criteria: List[EquiJoinClause] = dataclasses.field(default_factory=list)
    outputVariables: List[Variable] = dataclasses.field(default_factory=list)
    filter: Optional[Any] = None
    leftHashVariable: Optional[Variable] = None
    rightHashVariable: Optional[Variable] = None
    distributionType: Optional[str] = None
    dynamicFilters: Dict[str, Variable] = dataclasses.field(
        default_factory=dict)
    _SCHEMA = [
        ("id", "id", None),
        ("type", "type", None),
        ("left", "left", PlanNode),
        ("right", "right", PlanNode),
        ("criteria", "criteria", ("list", EquiJoinClause)),
        ("outputVariables", "outputVariables", ("list", Variable)),
        ("filter", "filter", ("opt", RowExpr)),
        ("leftHashVariable", "leftHashVariable", ("opt", Variable)),
        ("rightHashVariable", "rightHashVariable", ("opt", Variable)),
        ("distributionType", "distributionType", ("opt", None)),
        ("dynamicFilters", "dynamicFilters", ("map", Variable)),
    ]


@PlanNode.register(".SemiJoinNode")
@dataclasses.dataclass
class SemiJoinNode(PlanNode):
    id: str = ""
    source: Any = None
    filteringSource: Any = None
    sourceJoinVariable: Variable = None
    filteringSourceJoinVariable: Variable = None
    semiJoinOutput: Variable = None
    sourceHashVariable: Optional[Variable] = None
    filteringSourceHashVariable: Optional[Variable] = None
    distributionType: Optional[str] = None
    dynamicFilters: Dict[str, Variable] = dataclasses.field(
        default_factory=dict)
    # Engine extensions (absent in coordinator JSON, defaulting to Presto
    # semantics): xSemiKind SEMI|ANTI|ANTI_EXISTS carries the NOT-IN /
    # NOT-EXISTS null semantics this engine plans as distinct join kinds
    # (the Java planner expresses them as SemiJoin + surrounding
    # filters); xEmitFlag=False means the worker filters internally and
    # omits the semiJoinOutput column. Precedent: the C++ worker's
    # extension operators (presto_cpp/main/operators/).
    xSemiKind: Optional[str] = None
    xEmitFlag: Optional[bool] = None
    _SCHEMA = [
        ("id", "id", None),
        ("source", "source", PlanNode),
        ("filteringSource", "filteringSource", PlanNode),
        ("sourceJoinVariable", "sourceJoinVariable", Variable),
        ("filteringSourceJoinVariable", "filteringSourceJoinVariable",
         Variable),
        ("semiJoinOutput", "semiJoinOutput", Variable),
        ("sourceHashVariable", "sourceHashVariable", ("opt", Variable)),
        ("filteringSourceHashVariable", "filteringSourceHashVariable",
         ("opt", Variable)),
        ("distributionType", "distributionType", ("opt", None)),
        ("dynamicFilters", "dynamicFilters", ("map", Variable)),
        ("xSemiKind", "xSemiKind", ("opt", None)),
        ("xEmitFlag", "xEmitFlag", ("opt", None)),
    ]


@PlanNode.register(".LimitNode")
@dataclasses.dataclass
class LimitNode(PlanNode):
    id: str = ""
    source: Any = None
    count: int = 0
    step: str = "FINAL"
    _SCHEMA = [
        ("id", "id", None),
        ("source", "source", PlanNode),
        ("count", "count", None),
        ("step", "step", None),
    ]


@PlanNode.register(".TopNNode")
@dataclasses.dataclass
class TopNNode(PlanNode):
    id: str = ""
    source: Any = None
    count: int = 0
    orderingScheme: OrderingScheme = None
    step: str = "SINGLE"
    _SCHEMA = [
        ("id", "id", None),
        ("source", "source", PlanNode),
        ("count", "count", None),
        ("orderingScheme", "orderingScheme", OrderingScheme),
        ("step", "step", None),
    ]


@PlanNode.register(".SortNode")
@dataclasses.dataclass
class SortNode(PlanNode):
    id: str = ""
    source: Any = None
    orderingScheme: OrderingScheme = None
    isPartial: bool = False
    partitionBy: List[Variable] = dataclasses.field(default_factory=list)
    _SCHEMA = [
        ("id", "id", None),
        ("source", "source", PlanNode),
        ("orderingScheme", "orderingScheme", OrderingScheme),
        ("isPartial", "isPartial", None),
        ("partitionBy", "partitionBy", ("list", Variable)),
    ]


@PlanNode.register(".ValuesNode")
@dataclasses.dataclass
class ValuesNode(PlanNode):
    location: Any = None
    id: str = ""
    outputVariables: List[Variable] = dataclasses.field(default_factory=list)
    rows: List[List[Any]] = dataclasses.field(default_factory=list)
    valuesNodeLabel: Optional[str] = None
    _SCHEMA = [
        ("location", "location", ("opt", None)),
        ("id", "id", None),
        ("outputVariables", "outputVariables", ("list", Variable)),
        ("rows", "rows", ("listlist", RowExpr)),
        ("valuesNodeLabel", "valuesNodeLabel", ("opt", None)),
    ]


@PlanNode.register("com.facebook.presto.sql.planner.plan.ExchangeNode")
@dataclasses.dataclass
class ExchangeNode(PlanNode):
    id: str = ""
    type: str = "REPARTITION"            # GATHER | REPARTITION | REPLICATE
    scope: str = "LOCAL"                 # LOCAL | REMOTE_STREAMING | ...
    partitioningScheme: PartitioningScheme = None
    sources: List[Any] = dataclasses.field(default_factory=list)
    inputs: List[List[Variable]] = dataclasses.field(default_factory=list)
    ensureSourceOrdering: bool = False
    orderingScheme: Optional[OrderingScheme] = None
    _SCHEMA = [
        ("id", "id", None),
        ("type", "type", None),
        ("scope", "scope", None),
        ("partitioningScheme", "partitioningScheme", PartitioningScheme),
        ("sources", "sources", ("list", PlanNode)),
        ("inputs", "inputs", ("listlist", Variable)),
        ("ensureSourceOrdering", "ensureSourceOrdering", None),
        ("orderingScheme", "orderingScheme", ("opt", OrderingScheme)),
    ]


@PlanNode.register("com.facebook.presto.sql.planner.plan.RemoteSourceNode")
@dataclasses.dataclass
class RemoteSourceNode(PlanNode):
    id: str = ""
    sourceFragmentIds: List[str] = dataclasses.field(default_factory=list)
    outputVariables: List[Variable] = dataclasses.field(default_factory=list)
    ensureSourceOrdering: bool = False
    orderingScheme: Optional[OrderingScheme] = None
    exchangeType: str = "REPARTITION"
    encoding: str = "COLUMNAR"
    transportType: Optional[str] = "HTTP"
    _SCHEMA = [
        ("id", "id", None),
        ("sourceFragmentIds", "sourceFragmentIds", None),
        ("outputVariables", "outputVariables", ("list", Variable)),
        ("ensureSourceOrdering", "ensureSourceOrdering", None),
        ("orderingScheme", "orderingScheme", ("opt", OrderingScheme)),
        ("exchangeType", "exchangeType", None),
        ("encoding", "encoding", None),
        ("transportType", "transportType", ("opt", None)),
    ]


@PlanNode.register(".GroupIdNode")
@dataclasses.dataclass
class GroupIdNode(PlanNode):
    """spi/plan/GroupIdNode (simplified to this engine's pass-through
    layout): output = inputVariables (group keys nulled per set) ++
    groupIdVariable; groupingSets name subsets of inputVariables."""
    id: str = ""
    source: Any = None
    inputVariables: List[Variable] = dataclasses.field(default_factory=list)
    groupingSets: List[List[Variable]] = dataclasses.field(
        default_factory=list)
    groupIdVariable: Variable = None
    _SCHEMA = [
        ("id", "id", None),
        ("source", "source", PlanNode),
        ("inputVariables", "inputVariables", ("list", Variable)),
        ("groupingSets", "groupingSets", ("listlist", Variable)),
        ("groupIdVariable", "groupIdVariable", Variable),
    ]


@dataclasses.dataclass
class WindowFunction(Struct):
    """spi/plan/WindowNode.Function — functionCall + frame (frame fixed to
    the engine's supported RANGE UNBOUNDED PRECEDING..CURRENT ROW)."""
    functionCall: Call = None
    frame: Any = None
    ignoreNulls: bool = False
    _SCHEMA = [
        ("functionCall", "functionCall", Call),
        ("frame", "frame", ("opt", None)),
        ("ignoreNulls", "ignoreNulls", None),
    ]


@dataclasses.dataclass
class WindowSpecification(Struct):
    partitionBy: List[Variable] = dataclasses.field(default_factory=list)
    orderingScheme: Optional[OrderingScheme] = None
    _SCHEMA = [
        ("partitionBy", "partitionBy", ("list", Variable)),
        ("orderingScheme", "orderingScheme", ("opt", OrderingScheme)),
    ]


@PlanNode.register(".WindowNode")
@dataclasses.dataclass
class WindowNode(PlanNode):
    id: str = ""
    source: Any = None
    specification: WindowSpecification = None
    windowFunctions: Dict[str, WindowFunction] = dataclasses.field(
        default_factory=dict)
    hashVariable: Optional[Variable] = None
    prePartitionedInputs: List[Variable] = dataclasses.field(
        default_factory=list)
    preSortedOrderPrefix: int = 0
    _SCHEMA = [
        ("id", "id", None),
        ("source", "source", PlanNode),
        ("specification", "specification", WindowSpecification),
        ("windowFunctions", "windowFunctions", ("map", WindowFunction)),
        ("hashVariable", "hashVariable", ("opt", Variable)),
        ("prePartitionedInputs", "prePartitionedInputs",
         ("list", Variable)),
        ("preSortedOrderPrefix", "preSortedOrderPrefix", None),
    ]


@PlanNode.register(".AssignUniqueId")
@dataclasses.dataclass
class AssignUniqueIdNode(PlanNode):
    id: str = ""
    source: Any = None
    idVariable: Variable = None
    _SCHEMA = [
        ("id", "id", None),
        ("source", "source", PlanNode),
        ("idVariable", "idVariable", Variable),
    ]


@PlanNode.register(".EnforceSingleRowNode")
@dataclasses.dataclass
class EnforceSingleRowNode(PlanNode):
    id: str = ""
    source: Any = None
    _SCHEMA = [("id", "id", None), ("source", "source", PlanNode)]


@PlanNode.register(".TableWriterNode")
@dataclasses.dataclass
class TableWriterNode(PlanNode):
    """spi/plan/TableWriterNode.java (the fields this worker consumes;
    target/statistics extensions ride raw)."""
    id: str = ""
    source: Any = None
    target: Any = None
    rowCountVariable: Variable = None
    fragmentVariable: Optional[Variable] = None
    tableCommitContextVariable: Optional[Variable] = None
    columns: List[Variable] = dataclasses.field(default_factory=list)
    columnNames: List[str] = dataclasses.field(default_factory=list)
    _SCHEMA = [
        ("id", "id", None),
        ("source", "source", PlanNode),
        ("target", "target", ("opt", None)),
        ("rowCountVariable", "rowCountVariable", Variable),
        ("fragmentVariable", "fragmentVariable", ("opt", Variable)),
        ("tableCommitContextVariable", "tableCommitContextVariable",
         ("opt", Variable)),
        ("columns", "columns", ("list", Variable)),
        ("columnNames", "columnNames", None),
    ]


@PlanNode.register(".TableFinishNode")
@dataclasses.dataclass
class TableFinishNode(PlanNode):
    """spi/plan/TableFinishNode.java — commits and emits the summed row
    count."""
    id: str = ""
    source: Any = None
    target: Any = None
    rowCountVariable: Variable = None
    _SCHEMA = [
        ("id", "id", None),
        ("source", "source", PlanNode),
        ("target", "target", ("opt", None)),
        ("rowCountVariable", "rowCountVariable", Variable),
    ]


@PlanNode.register(".UnionNode")
@dataclasses.dataclass
class UnionNode(PlanNode):
    """spi/plan/UnionNode.java (SetOperationNode shape): outputToInputs
    maps each output variable ("name<type>" key) to the per-source input
    variables, in source order."""
    id: str = ""
    sources: List[Any] = dataclasses.field(default_factory=list)
    outputVariables: List[Variable] = dataclasses.field(
        default_factory=list)
    outputToInputs: Dict[str, List[Variable]] = dataclasses.field(
        default_factory=dict)
    _SCHEMA = [
        ("id", "id", None),
        ("sources", "sources", ("list", PlanNode)),
        ("outputVariables", "outputVariables", ("list", Variable)),
        ("outputToInputs", "outputToInputs", ("map", ("list", Variable))),
    ]


@PlanNode.register(".MarkDistinctNode")
@dataclasses.dataclass
class MarkDistinctNode(PlanNode):
    """spi/plan/MarkDistinctNode.java."""
    id: str = ""
    source: Any = None
    markerVariable: Variable = None
    distinctVariables: List[Variable] = dataclasses.field(
        default_factory=list)
    hashVariable: Optional[Variable] = None
    _SCHEMA = [
        ("id", "id", None),
        ("source", "source", PlanNode),
        ("markerVariable", "markerVariable", Variable),
        ("distinctVariables", "distinctVariables", ("list", Variable)),
        ("hashVariable", "hashVariable", ("opt", Variable)),
    ]


@PlanNode.register(".UnnestNode")
@dataclasses.dataclass
class UnnestNode(PlanNode):
    """spi/plan/UnnestNode.java — unnestVariables maps each nested input
    variable ("name<type>" key) to its flattened output variables (1 for
    array, 2 for map)."""
    id: str = ""
    source: Any = None
    replicateVariables: List[Variable] = dataclasses.field(
        default_factory=list)
    unnestVariables: Dict[str, List[Variable]] = dataclasses.field(
        default_factory=dict)
    ordinalityVariable: Optional[Variable] = None
    _SCHEMA = [
        ("id", "id", None),
        ("source", "source", PlanNode),
        ("replicateVariables", "replicateVariables", ("list", Variable)),
        ("unnestVariables", "unnestVariables",
         ("map", ("list", Variable))),
        ("ordinalityVariable", "ordinalityVariable", ("opt", Variable)),
    ]


@PlanNode.register("com.facebook.presto.sql.planner.plan.RowNumberNode")
@dataclasses.dataclass
class RowNumberNode(PlanNode):
    """sql/planner/plan/RowNumberNode.java — fully-qualified @type because
    it lives outside spi/plan (Jackson MINIMAL_CLASS is relative to the
    spi.plan package). Seen in the reference's OffsetLimit.json capture
    (OFFSET is planned as row_number + filter)."""
    id: str = ""
    source: Any = None
    partitionBy: List[Variable] = dataclasses.field(default_factory=list)
    rowNumberVariable: Variable = None
    maxRowCountPerPartition: Optional[int] = None
    partial: bool = False
    hashVariable: Optional[Variable] = None
    _SCHEMA = [
        ("id", "id", None),
        ("source", "source", PlanNode),
        ("partitionBy", "partitionBy", ("list", Variable)),
        ("rowNumberVariable", "rowNumberVariable", Variable),
        ("maxRowCountPerPartition", "maxRowCountPerPartition",
         ("opt", None)),
        ("partial", "partial", None),
        ("hashVariable", "hashVariable", ("opt", Variable)),
    ]


@PlanNode.register(".IndexSourceNode")
@dataclasses.dataclass
class IndexSourceNode(PlanNode):
    """spi/plan/IndexSourceNode.java — parsed so the validator can reject
    index joins with a precise message (the TPU worker has no connector
    index lookup; mirrors VeloxPlanValidator's unsupported-node path)."""
    id: str = ""
    indexHandle: Any = None
    tableHandle: Any = None
    lookupVariables: List[Variable] = dataclasses.field(default_factory=list)
    outputVariables: List[Variable] = dataclasses.field(default_factory=list)
    assignments: Dict[str, Any] = dataclasses.field(default_factory=dict)
    currentConstraint: Any = None
    _SCHEMA = [
        ("id", "id", None),
        ("indexHandle", "indexHandle", None),
        ("tableHandle", "tableHandle", None),
        ("lookupVariables", "lookupVariables", ("list", Variable)),
        ("outputVariables", "outputVariables", ("list", Variable)),
        ("assignments", "assignments", None),
        ("currentConstraint", "currentConstraint", ("opt", None)),
    ]


# ---------------------------------------------------------------------------
# PlanFragment / TaskUpdateRequest / task metadata
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class StageExecutionDescriptor(Struct):
    stageExecutionStrategy: str = "UNGROUPED_EXECUTION"
    groupedExecutionScanNodes: List[str] = dataclasses.field(
        default_factory=list)
    totalLifespans: int = 1
    _SCHEMA = [
        ("stageExecutionStrategy", "stageExecutionStrategy", None),
        ("groupedExecutionScanNodes", "groupedExecutionScanNodes", None),
        ("totalLifespans", "totalLifespans", None),
    ]


@dataclasses.dataclass
class PlanFragment(Struct):
    id: str = "0"
    root: Any = None
    variables: List[Variable] = dataclasses.field(default_factory=list)
    partitioning: PartitioningHandle = None
    tableScanSchedulingOrder: List[str] = dataclasses.field(
        default_factory=list)
    partitioningScheme: PartitioningScheme = None
    outputOrderingScheme: Optional[OrderingScheme] = None
    stageExecutionDescriptor: StageExecutionDescriptor = None
    outputTableWriterFragment: bool = False
    outputTransportType: Optional[str] = "HTTP"
    statsAndCosts: Any = None
    jsonRepresentation: Optional[str] = None
    _SCHEMA = [
        ("id", "id", None),
        ("root", "root", PlanNode),
        ("variables", "variables", ("list", Variable)),
        ("partitioning", "partitioning", PartitioningHandle),
        ("tableScanSchedulingOrder", "tableScanSchedulingOrder", None),
        ("partitioningScheme", "partitioningScheme", PartitioningScheme),
        ("outputOrderingScheme", "outputOrderingScheme",
         ("opt", OrderingScheme)),
        ("stageExecutionDescriptor", "stageExecutionDescriptor",
         StageExecutionDescriptor),
        ("outputTableWriterFragment", "outputTableWriterFragment", None),
        ("outputTransportType", "outputTransportType", ("opt", None)),
        ("statsAndCosts", "statsAndCosts", ("opt", None)),
        ("jsonRepresentation", "jsonRepresentation", ("opt", None)),
    ]

    def to_bytes(self) -> str:
        """base64(json) — how TaskUpdateRequest.fragment rides the wire."""
        return base64.b64encode(self.dumps().encode()).decode()

    @classmethod
    def from_bytes(cls, b64: str) -> "PlanFragment":
        return cls.loads(base64.b64decode(b64).decode())


@dataclasses.dataclass
class Split(Struct):
    connectorId: str = ""
    transactionHandle: Any = None
    connectorSplit: Any = None           # raw per-connector payload
    lifespan: Any = None
    splitContext: Any = None
    _SCHEMA = [
        ("connectorId", "connectorId", None),
        ("transactionHandle", "transactionHandle", ("opt", None)),
        ("connectorSplit", "connectorSplit", None),
        ("lifespan", "lifespan", ("opt", None)),
        ("splitContext", "splitContext", ("opt", None)),
    ]


@dataclasses.dataclass
class ScheduledSplit(Struct):
    sequenceId: int = 0
    planNodeId: str = ""
    split: Split = None
    _SCHEMA = [
        ("sequenceId", "sequenceId", None),
        ("planNodeId", "planNodeId", None),
        ("split", "split", Split),
    ]


@dataclasses.dataclass
class TaskSource(Struct):
    planNodeId: str = ""
    splits: List[ScheduledSplit] = dataclasses.field(default_factory=list)
    noMoreSplitsForLifespan: List[Any] = dataclasses.field(
        default_factory=list)
    noMoreSplits: bool = False
    _SCHEMA = [
        ("planNodeId", "planNodeId", None),
        ("splits", "splits", ("list", ScheduledSplit)),
        ("noMoreSplitsForLifespan", "noMoreSplitsForLifespan", None),
        ("noMoreSplits", "noMoreSplits", None),
    ]


@dataclasses.dataclass
class OutputBuffers(Struct):
    type: str = "PARTITIONED"            # PARTITIONED | BROADCAST | ARBITRARY
    version: int = 0
    noMoreBufferIds: bool = False
    buffers: Dict[str, int] = dataclasses.field(default_factory=dict)
    _SCHEMA = [
        ("type", "type", None),
        ("version", "version", None),
        ("noMoreBufferIds", "noMoreBufferIds", None),
        ("buffers", "buffers", None),
    ]


@dataclasses.dataclass
class SessionRepresentation(Struct):
    """The subset of session state this worker consumes; unknown properties
    round-trip via systemProperties/catalogProperties raw maps."""
    queryId: str = ""
    transactionId: Optional[str] = None
    clientTransactionSupport: bool = False
    user: str = "user"
    principal: Optional[str] = None
    source: Optional[str] = None
    catalog: Optional[str] = None
    schema: Optional[str] = None
    timeZoneKey: int = 0
    locale: str = "en"
    remoteUserAddress: Optional[str] = None
    userAgent: Optional[str] = None
    clientInfo: Optional[str] = None
    clientTags: List[str] = dataclasses.field(default_factory=list)
    resourceEstimates: Dict[str, Any] = dataclasses.field(
        default_factory=dict)
    startTime: int = 0
    systemProperties: Dict[str, str] = dataclasses.field(
        default_factory=dict)
    catalogProperties: Dict[str, Any] = dataclasses.field(
        default_factory=dict)
    unprocessedCatalogProperties: Dict[str, Any] = dataclasses.field(
        default_factory=dict)
    roles: Dict[str, Any] = dataclasses.field(default_factory=dict)
    preparedStatements: Dict[str, str] = dataclasses.field(
        default_factory=dict)
    sessionFunctions: Dict[str, Any] = dataclasses.field(
        default_factory=dict)
    _SCHEMA = [
        ("queryId", "queryId", None),
        ("transactionId", "transactionId", ("opt", None)),
        ("clientTransactionSupport", "clientTransactionSupport", None),
        ("user", "user", None),
        ("principal", "principal", ("opt", None)),
        ("source", "source", ("opt", None)),
        ("catalog", "catalog", ("opt", None)),
        ("schema", "schema", ("opt", None)),
        ("timeZoneKey", "timeZoneKey", None),
        ("locale", "locale", None),
        ("remoteUserAddress", "remoteUserAddress", ("opt", None)),
        ("userAgent", "userAgent", ("opt", None)),
        ("clientInfo", "clientInfo", ("opt", None)),
        ("clientTags", "clientTags", None),
        ("resourceEstimates", "resourceEstimates", None),
        ("startTime", "startTime", None),
        ("systemProperties", "systemProperties", None),
        ("catalogProperties", "catalogProperties", None),
        ("unprocessedCatalogProperties", "unprocessedCatalogProperties",
         None),
        ("roles", "roles", None),
        ("preparedStatements", "preparedStatements", None),
        ("sessionFunctions", "sessionFunctions", None),
    ]


@dataclasses.dataclass
class TaskUpdateRequest(Struct):
    session: SessionRepresentation = None
    extraCredentials: Dict[str, str] = dataclasses.field(
        default_factory=dict)
    fragment: Optional[str] = None       # base64(PlanFragment json)
    sources: List[TaskSource] = dataclasses.field(default_factory=list)
    outputIds: OutputBuffers = None
    tableWriteInfo: Any = None
    _SCHEMA = [
        ("session", "session", SessionRepresentation),
        ("extraCredentials", "extraCredentials", None),
        ("fragment", "fragment", ("opt", None)),
        ("sources", "sources", ("list", TaskSource)),
        ("outputIds", "outputIds", OutputBuffers),
        ("tableWriteInfo", "tableWriteInfo", ("opt", None)),
    ]


@dataclasses.dataclass
class BatchTaskUpdateRequest(Struct):
    """presto_protocol BatchTaskUpdateRequest — the Spark/batch-mode
    update envelope (presto_cpp/main/TaskResource.cpp:115-180
    /v1/task/{id}/batch): a TaskUpdateRequest plus optional shuffle
    read/write descriptors carried as raw JSON."""
    taskUpdateRequest: TaskUpdateRequest = None
    shuffleWriteInfo: Optional[str] = None
    broadcastBasePath: Optional[str] = None
    _SCHEMA = [
        ("taskUpdateRequest", "taskUpdateRequest", TaskUpdateRequest),
        ("shuffleWriteInfo", "shuffleWriteInfo", ("opt", None)),
        ("broadcastBasePath", "broadcastBasePath", ("opt", None)),
    ]


# ---------------------------------------------------------------------------
# Task status/info (worker -> coordinator)
# ---------------------------------------------------------------------------

TASK_STATES = ("PLANNED", "RUNNING", "FINISHED", "CANCELED", "ABORTED",
               "FAILED")


@dataclasses.dataclass(frozen=True)
class TaskId:
    """Structured task id (reference: execution/TaskId.java —
    queryId.stageId.stageExecutionId.taskId.attemptNumber). The attempt
    number is what makes stage-level retry addressable: a recovery
    re-post of the same (query, stage, index) work unit carries
    attempt N+1, and spool lookups match on everything BUT the attempt
    so a replacement consumer finds any committed attempt's output."""

    query_id: str
    stage_id: int
    stage_execution_id: int = 0
    task_index: int = 0
    attempt: int = 0

    @classmethod
    def parse(cls, s: str) -> "TaskId":
        parts = s.rsplit(".", 4)
        if len(parts) != 5 or not parts[0]:
            raise ValueError(f"malformed task id {s!r}")
        try:
            return cls(parts[0], int(parts[1]), int(parts[2]),
                       int(parts[3]), int(parts[4]))
        except ValueError:
            raise ValueError(f"malformed task id {s!r}") from None

    def __str__(self) -> str:
        return (f"{self.query_id}.{self.stage_id}."
                f"{self.stage_execution_id}.{self.task_index}."
                f"{self.attempt}")

    def with_attempt(self, attempt: int) -> "TaskId":
        return dataclasses.replace(self, attempt=attempt)


@dataclasses.dataclass
class TaskStatus(Struct):
    taskInstanceIdLeastSignificantBits: int = 0
    taskInstanceIdMostSignificantBits: int = 0
    version: int = 1
    state: str = "PLANNED"
    self_uri: str = ""
    completedDriverGroups: List[Any] = dataclasses.field(
        default_factory=list)
    failures: List[Any] = dataclasses.field(default_factory=list)
    queuedPartitionedDrivers: int = 0
    runningPartitionedDrivers: int = 0
    outputBufferUtilization: float = 0.0
    outputBufferOverutilized: bool = False
    physicalWrittenDataSizeInBytes: int = 0
    memoryReservationInBytes: int = 0
    systemMemoryReservationInBytes: int = 0
    peakNodeTotalMemoryReservationInBytes: int = 0
    fullGcCount: int = 0
    fullGcTimeInMillis: int = 0
    totalCpuTimeInNanos: int = 0
    taskAgeInMillis: int = 0
    queuedPartitionedSplitsWeight: int = 0
    runningPartitionedSplitsWeight: int = 0
    #: span clock (epoch seconds) of the last state transition: the
    #: coordinator's `task_notice` starts there; a worker that does not
    #: say (the reference's) leaves it out
    stateChangedAt: Optional[float] = None
    _SCHEMA = [
        ("taskInstanceIdLeastSignificantBits",
         "taskInstanceIdLeastSignificantBits", None),
        ("taskInstanceIdMostSignificantBits",
         "taskInstanceIdMostSignificantBits", None),
        ("version", "version", None),
        ("state", "state", None),
        ("self_uri", "self", None),
        ("completedDriverGroups", "completedDriverGroups", None),
        ("failures", "failures", None),
        ("queuedPartitionedDrivers", "queuedPartitionedDrivers", None),
        ("runningPartitionedDrivers", "runningPartitionedDrivers", None),
        ("outputBufferUtilization", "outputBufferUtilization", None),
        ("outputBufferOverutilized", "outputBufferOverutilized", None),
        ("physicalWrittenDataSizeInBytes",
         "physicalWrittenDataSizeInBytes", None),
        ("memoryReservationInBytes", "memoryReservationInBytes", None),
        ("systemMemoryReservationInBytes",
         "systemMemoryReservationInBytes", None),
        ("peakNodeTotalMemoryReservationInBytes",
         "peakNodeTotalMemoryReservationInBytes", None),
        ("fullGcCount", "fullGcCount", None),
        ("fullGcTimeInMillis", "fullGcTimeInMillis", None),
        ("totalCpuTimeInNanos", "totalCpuTimeInNanos", None),
        ("taskAgeInMillis", "taskAgeInMillis", None),
        ("queuedPartitionedSplitsWeight",
         "queuedPartitionedSplitsWeight", None),
        ("runningPartitionedSplitsWeight",
         "runningPartitionedSplitsWeight", None),
        ("stateChangedAt", "stateChangedAt", ("opt", None)),
    ]


@dataclasses.dataclass
class TaskInfo(Struct):
    taskId: str = ""
    taskStatus: TaskStatus = None
    lastHeartbeatInMillis: int = 0
    outputBuffers: Any = None
    noMoreSplits: List[str] = dataclasses.field(default_factory=list)
    stats: Any = None
    needsPlan: bool = False
    metadataUpdates: Any = None
    nodeId: str = ""
    _SCHEMA = [
        ("taskId", "taskId", None),
        ("taskStatus", "taskStatus", TaskStatus),
        ("lastHeartbeatInMillis", "lastHeartbeatInMillis", None),
        ("outputBuffers", "outputBuffers", ("opt", None)),
        ("noMoreSplits", "noMoreSplits", None),
        ("stats", "stats", ("opt", None)),
        ("needsPlan", "needsPlan", None),
        ("metadataUpdates", "metadataUpdates", ("opt", None)),
        ("nodeId", "nodeId", None),
    ]
