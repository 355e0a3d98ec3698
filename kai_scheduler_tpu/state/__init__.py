from .cluster_state import (  # noqa: F401
    ClusterState,
    GangState,
    NodeState,
    QueueState,
    RunningState,
    SnapshotCapacity,
    SnapshotIndex,
    SnapshotVocabulary,
    build_snapshot,
)
from .incremental import (  # noqa: F401
    IncrementalSnapshotter,
    IncrementalVerifyError,
    MutationJournal,
)
from .synthetic import make_cluster  # noqa: F401
