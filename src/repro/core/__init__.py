"""Core contribution: concurrent-kernel launch reordering (Algorithm 1).

Faithful reproduction of Li/Narayana/El-Ghazawi 2015 plus the TPU
adaptation used by the serving and training substrates.
"""

from .resources import (GTX580, TPU_V5E_UNIT, DeviceModel, KernelProfile,
                        bs_kernel, ep_kernel, es_kernel, sw_kernel)
from .scorer import (combined_ratio, fits_alone, fits_together, pair_score,
                     profile_combine, score_matrix, score_vector)
from .scheduler import (Round, Schedule, exhaustive_search, greedy_order,
                        percentile_rank, random_orders)
from .simulator import (EventCheckpoint, EventSimulator, RoundCheckpoint,
                        RoundSimulator, simulate)
from .experiments import EXPERIMENTS, experiment
from .fastscore import (ProfileTable, greedy_order_fast, pair_score_matrix,
                        score_matrix_fast, warm_start_insert)
from .refine import DeltaEvaluator, refine_order, refined_schedule
from .tpu import (TpuWorkItem, compose_rounds, decode_profile,
                  make_serving_device, prefill_profile)

__all__ = [
    "GTX580", "TPU_V5E_UNIT", "DeviceModel", "KernelProfile",
    "bs_kernel", "ep_kernel", "es_kernel", "sw_kernel",
    "combined_ratio", "fits_alone", "fits_together", "pair_score",
    "profile_combine", "score_matrix", "score_vector",
    "Round", "Schedule", "exhaustive_search", "greedy_order",
    "percentile_rank", "random_orders",
    "EventCheckpoint", "EventSimulator", "RoundCheckpoint",
    "RoundSimulator", "simulate",
    "EXPERIMENTS", "experiment",
    "ProfileTable", "greedy_order_fast", "pair_score_matrix",
    "score_matrix_fast", "warm_start_insert",
    "DeltaEvaluator", "refine_order", "refined_schedule",
    "TpuWorkItem", "compose_rounds", "decode_profile",
    "make_serving_device", "prefill_profile",
]
