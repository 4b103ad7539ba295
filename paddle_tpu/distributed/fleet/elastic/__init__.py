"""``fleet.elastic`` — preemption-proof elastic training.

The supervisor loop over the pieces PRs 4/6/7/8 built: in-process
device preflight with a deadline (:mod:`.preflight`), a supervised
step loop under the stall watchdog + cluster health plane with
failure classification and elastic restore on the surviving topology
(:mod:`.supervisor`), and injectable faults so every recovery path is
rehearsed continuously (:mod:`.chaos`).  See the README "Elastic
training" section for the lifecycle and the flag reference
(``FLAGS_elastic_max_restarts`` / ``FLAGS_elastic_preflight_timeout_s``
/ ``FLAGS_elastic_backoff_s``).
"""
from __future__ import annotations

from . import chaos
from .chaos import RankKilled, TornCheckpoint
from .preflight import (PREFLIGHT_COMPILE_ERROR,
                        PREFLIGHT_INIT_TIMEOUT, PREFLIGHT_OK,
                        PreflightVerdict, default_probe,
                        preflight_device)
from .supervisor import (FAILURE_POISON, FAILURE_TOPOLOGY,
                         FAILURE_TRANSIENT, DeadRankDetected,
                         ElasticSupervisor, ElasticTerminated,
                         PreflightError, StallDetected, SupervisorResult,
                         Topology, classify_failure,
                         dead_ranks_from_cluster, is_device_failure)

__all__ = [
    "ElasticSupervisor", "SupervisorResult", "Topology",
    "ElasticTerminated", "PreflightError", "StallDetected",
    "DeadRankDetected", "RankKilled", "TornCheckpoint",
    "preflight_device", "PreflightVerdict", "default_probe",
    "PREFLIGHT_OK", "PREFLIGHT_INIT_TIMEOUT", "PREFLIGHT_COMPILE_ERROR",
    "classify_failure", "is_device_failure", "dead_ranks_from_cluster",
    "FAILURE_TRANSIENT", "FAILURE_TOPOLOGY", "FAILURE_POISON", "chaos",
]
