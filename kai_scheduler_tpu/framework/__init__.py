from .scheduler import (CycleResult, Scheduler, SchedulerConfig,
                        action_names)
from .session import Session, SessionConfig

__all__ = [
    "CycleResult", "Scheduler", "SchedulerConfig", "Session",
    "SessionConfig", "action_names",
]
