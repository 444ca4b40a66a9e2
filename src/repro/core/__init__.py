"""PALPATINE core: the paper's contribution.

Frequent-sequence mining over intercepted DKV access logs (VMSP + the
compared algorithm families), probabilistic trees, prefetching heuristics,
and the two-space application-level cache — plus the simulated HBase-like
back store used by the paper-fidelity benchmarks.
"""

from .api import Client
from .backstore import Channel, Clock, LatencyModel, RPCFuture, SimulatedDKVStore
from .cache import CacheStats, TwoSpaceCache
from .chaos import ChaosEngine, ChaosSchedule, Fault
from .cluster import (
    ClusterBaseline,
    ClusterClient,
    ClusterConfig,
    PatternExchange,
    ShardedDKVStore,
    ShardedTwoSpaceCache,
    VerdictExchange,
)
from .decision import VectorizedPrefetchEngine
from .heuristics import HEURISTICS, HeuristicConfig, PrefetchEngine
from .membership import (
    BudgetRebalancer,
    FailureDetector,
    HintedHandoffLog,
    LeaseConflict,
    LeaseTable,
    MembershipEvent,
    MoveReport,
    RangeLease,
)
from .metastore import PatternMetastore, VerdictBoard
from .obs import (
    NULL_TRACER,
    AttributionTable,
    Histogram,
    MetricsRegistry,
    NullTracer,
    PrefetchCause,
    Span,
    Tracer,
    critical_path,
    latency_percentiles,
    percentile,
    span_kind_breakdown,
)
from .versions import DottedVersion, concurrent, descends, merge
from .mining import (
    ALGORITHMS,
    BITMAP_ALGOS,
    MiningParams,
    Pattern,
    VerticalBitmaps,
    brute_force,
    mine,
    mine_dynamic_minsup,
)
from .palpatine import BaselineClient, PalpatineClient, PalpatineConfig
from .ptree import FlatForest, PTree, PTreeIndex
from .sessions import AccessLogger, Container, SequenceDatabase

__all__ = [
    "AccessLogger", "ALGORITHMS", "AttributionTable", "BITMAP_ALGOS",
    "BaselineClient",
    "BudgetRebalancer",
    "Histogram", "MetricsRegistry", "NULL_TRACER", "NullTracer",
    "PrefetchCause", "Span", "Tracer",
    "critical_path", "latency_percentiles", "percentile",
    "span_kind_breakdown",
    "CacheStats", "Channel", "ChaosEngine", "ChaosSchedule", "Client",
    "Clock", "DottedVersion", "FailureDetector", "Fault", "FlatForest",
    "HintedHandoffLog",
    "LeaseConflict",
    "LeaseTable", "MembershipEvent", "MoveReport", "RangeLease",
    "RPCFuture",
    "ClusterBaseline", "ClusterClient", "ClusterConfig", "Container",
    "HEURISTICS", "HeuristicConfig", "LatencyModel",
    "MiningParams", "Pattern", "PatternExchange", "PatternMetastore",
    "PalpatineClient", "PalpatineConfig", "PrefetchEngine", "PTree",
    "PTreeIndex", "SequenceDatabase", "ShardedDKVStore",
    "ShardedTwoSpaceCache", "SimulatedDKVStore", "TwoSpaceCache",
    "VectorizedPrefetchEngine", "VerdictBoard", "VerdictExchange",
    "VerticalBitmaps", "brute_force",
    "concurrent", "descends", "merge",
    "mine", "mine_dynamic_minsup",
]
