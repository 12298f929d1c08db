"""Microbenchmark probes (paper contribution C2)."""
from .runners import (HostRunner, ProbeRunner, SimRunner, SpaceInfo,
                      random_cycle, sattolo_cycle)
from .chaos import ChaosRunner, FaultSchedule
from .pallas_runner import PallasRunner, make_pallas_model
from .tpu_runner import TpuRunner
from .size import SizeResult, find_size
from .latency import LatencyResult, measure_latency
from .linesize import (GranularityResult, LineSizeResult,
                       find_fetch_granularity, find_line_size, snap_pow2)
from .amount import (AmountResult, CuSharingResult, SharingResult,
                     align_segments, find_amount, find_cu_sharing, find_sharing)
from .bandwidth import (BandwidthResult, CollectiveEstimate, all_to_all_time,
                        measure_bandwidth, measure_collective,
                        ring_all_gather_time, ring_all_reduce_time)
from .adjacency import AdjacencyResult, SimPod, find_link_adjacency

__all__ = [
    "ChaosRunner", "FaultSchedule",
    "HostRunner", "PallasRunner", "ProbeRunner", "SimRunner", "SpaceInfo",
    "TpuRunner", "make_pallas_model", "random_cycle", "sattolo_cycle",
    "SizeResult", "find_size", "LatencyResult", "measure_latency",
    "GranularityResult", "LineSizeResult", "find_fetch_granularity",
    "find_line_size", "snap_pow2",
    "AmountResult", "CuSharingResult", "SharingResult", "align_segments",
    "find_amount", "find_cu_sharing", "find_sharing",
    "BandwidthResult", "CollectiveEstimate", "all_to_all_time",
    "measure_bandwidth", "measure_collective", "ring_all_gather_time",
    "ring_all_reduce_time",
    "AdjacencyResult", "SimPod", "find_link_adjacency",
]
