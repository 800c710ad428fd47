"""Cross-device cohort subsystem: MOCHA over 10^5-10^6-client populations.

Everything above the round -- population storage, cohort sampling,
relationship factorization -- at O(m + k^2) memory; everything at and below
the round is the unchanged cross-silo machinery (DESIGN.md section 7).
"""
from repro.cohort.driver import (COHORT_HISTORY_KEYS, CohortConfig,
                                 CohortRunResult)
from repro.cohort.omega import ClusterOmega, StalenessBoundedMerger
from repro.cohort.packing import CohortPacker, pack_cohort
from repro.cohort.resilience import (BlockFailure, CohortCheckpointer,
                                     FaultConfig, FaultPlan, FaultStats,
                                     InjectedFault)
from repro.cohort.population import (CROSS_DEVICE_1K, CROSS_DEVICE_1M,
                                     CROSS_DEVICE_10K, CROSS_DEVICE_100K,
                                     POPULATIONS, ClientBlock, Population,
                                     PopulationSpec)
from repro.cohort.sampler import SAMPLERS, CohortSampler, CohortSchedule
