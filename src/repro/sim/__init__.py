"""repro.sim — fleet-scale adaptive-splitting simulation engine."""
from repro.sim.cells import (CellsResult, attach_ring, build_cells_episode,
                             cell_load, coupled_interference_mw,
                             handover_grid, jain_index, ring_coupling,
                             simulate_cells)
from repro.sim.engine import (FleetResult, TP_CLIP_MBPS, emit_period_samples,
                              estimate_fleet, run_controllers, run_scheduled,
                              simulate_fleet, simulate_fleet_looped,
                              split_metrics)
from repro.sim.online import (DriftConfig, DriftState, OnlineConfig,
                              OnlineStats, ReplayBuffer, ReplayBufferSSM,
                              buffer_add, buffer_add_masked, buffer_add_ssm,
                              buffer_count, buffer_data, buffer_init,
                              drift_init, drift_step, drift_threshold,
                              online_estimate_fleet, online_step_program)
from repro.sim.pool import (LifecycleStats, PoolPrograms, PoolState,
                            pool_init, pool_programs, simulate_pool)
from repro.sim.sched import (POLICIES, SchedulerConfig, SchedulerState,
                             cell_shares, scheduler_init, scheduler_step)
from repro.sim.serving import (ServingMesh, make_serving_mesh,
                               replicate_params, serving_program,
                               sharded_fleet_estimate,
                               sharded_ssm_estimate, ssm_serving_program)
from repro.sim.telemetry import (EVENT_NAMES, HostTelemetry, StageStat,
                                 TelemetryConfig, TelemetryEvent,
                                 TelemetryRecord, TelemetryState,
                                 telemetry_decode, telemetry_init,
                                 telemetry_step, timed,
                                 to_jsonl, to_prometheus, trace_capture)
from repro.sim.telemetry import stage as telemetry_stage

__all__ = ["CellsResult", "DriftConfig", "DriftState", "EVENT_NAMES",
           "FleetResult", "HostTelemetry",
           "LifecycleStats", "OnlineConfig", "OnlineStats", "POLICIES",
           "PoolPrograms", "PoolState", "ReplayBuffer", "ReplayBufferSSM",
           "SchedulerConfig",
           "SchedulerState", "ServingMesh", "StageStat",
           "TP_CLIP_MBPS", "TelemetryConfig", "TelemetryEvent",
           "TelemetryRecord", "TelemetryState", "attach_ring",
           "buffer_add", "buffer_add_masked", "buffer_add_ssm",
           "buffer_count", "buffer_data",
           "buffer_init", "build_cells_episode", "cell_load", "cell_shares",
           "coupled_interference_mw", "drift_init", "drift_step",
           "drift_threshold", "emit_period_samples", "estimate_fleet",
           "handover_grid", "jain_index", "make_serving_mesh",
           "online_estimate_fleet", "online_step_program", "pool_init",
           "pool_programs", "replicate_params", "ring_coupling",
           "run_controllers", "run_scheduled", "scheduler_init",
           "scheduler_step", "serving_program", "sharded_fleet_estimate",
           "sharded_ssm_estimate", "ssm_serving_program",
           "simulate_cells", "simulate_fleet", "simulate_fleet_looped",
           "simulate_pool", "split_metrics", "telemetry_decode",
           "telemetry_init", "telemetry_stage", "telemetry_step", "timed",
           "to_jsonl", "to_prometheus", "trace_capture"]
