"""FLECS-CGD core: the paper's primary contribution as a composable library.

Declarative method registry + experiment plans (one compile per figure):
    from repro.core.api import ExperimentPlan, MethodRun, get_method, run_plan
Traced compressor algebra (specs as vmappable sweep axes; make_spec is
the one constructor — names, specs, and Compressors all normalize there):
    from repro.core.compressors import make_spec, compress, spec_bits
Exact mode (paper-scale problems):
    from repro.core.flecs import FlecsConfig, init_state, make_flecs_sweep_step
Experiment engine (lax.scan runs, client sampling, vmapped sweeps, the
buffered-async engine):
    from repro.core.driver import run_experiment, run_sweep, make_async_step
Production traffic simulation (arrivals, availability, admission):
    from repro.core.traffic import TrafficModel, ArrivalSchedule
DL-scale trainer (TPU-pod realization):
    from repro.core.dl_flecs import FlecsDLConfig, make_flecs_train_step

NOTE: ``repro.core.api`` is intentionally NOT imported here — it pulls
``repro.optim.baselines`` (the whole baseline suite) into every core
import; import it explicitly.
"""
from repro.core.compressors import (FAMILY_COUNT_SKETCH, FAMILY_DITHER,
                                    FAMILY_IDENTITY, FAMILY_MINMAX,
                                    FAMILY_NATURAL, FAMILY_TOPK,
                                    Compressor, CompressorSpec, compress,
                                    count_sketch_decode, count_sketch_encode,
                                    count_sketch_spec, dither_spec,
                                    fill_params, get_compressor,
                                    identity_spec, make_spec, minmax_spec,
                                    natural_spec, psum_level_cap,
                                    SketchParams, spec_bits, spec_bits_many,
                                    spec_commutes_with_sum, spec_from_name,
                                    spec_omega, stack_specs, topk_spec)
from repro.core.driver import (COHORT_SALT, AsyncHParams, AsyncHooks,
                               AsyncState, cohort_indices, damped_alpha,
                               freeze_on_bit_budget, hparams_bit_budget,
                               init_async_state, iters_for_bit_budget,
                               make_async_step, participation_mask,
                               resolve_participation, run_async_sweep,
                               run_experiment, run_sharded_sweep, run_sweep,
                               sweep_keys, sweep_program, worker_mesh)
from repro.core.flecs import (FlecsCohortState, FlecsConfig, FlecsHParams,
                              FlecsState, async_hparam_grid, bits_per_round,
                              hparam_grid, hparams_round_bits,
                              init_cohort_state, init_state,
                              make_flecs_async_hooks,
                              make_flecs_cohort_sweep_step,
                              make_flecs_sharded_sweep_step,
                              make_flecs_sweep_step, sharded_state_specs)
from repro.core.hierarchy import (EDGE_SALT, HierarchyConfig, charge_edges,
                                  edge_combine, edge_combine_cohort,
                                  edge_of, edge_round_bits, init_edge_bits,
                                  validate_hierarchy)
from repro.core.sketch import sketch
from repro.core.traffic import (ARRIVAL_SALT, AVAIL_SALT, AVAILABLE, BUSY,
                                DROPPED, AdmissionPolicy, ArrivalSchedule,
                                AvailabilityModel, TrafficHParams,
                                TrafficModel, TrafficState, admit_arrivals,
                                availability_step, available_mask,
                                init_traffic_state, replay_delays,
                                stationary_distribution, thinned_delays,
                                traffic_hparams, traffic_send)

__all__ = ["Compressor", "CompressorSpec", "FAMILY_COUNT_SKETCH",
           "FAMILY_DITHER", "FAMILY_IDENTITY", "FAMILY_MINMAX",
           "FAMILY_NATURAL", "FAMILY_TOPK", "SketchParams", "compress",
           "count_sketch_decode", "count_sketch_encode", "count_sketch_spec",
           "dither_spec", "fill_params", "get_compressor", "identity_spec",
           "make_spec", "minmax_spec", "natural_spec",
           "psum_level_cap", "spec_bits", "spec_bits_many",
           "spec_commutes_with_sum", "spec_from_name", "spec_omega",
           "stack_specs", "topk_spec",
           "ARRIVAL_SALT", "AVAILABLE", "AVAIL_SALT", "AdmissionPolicy",
           "ArrivalSchedule", "AvailabilityModel", "BUSY",
           "AsyncHParams", "AsyncHooks", "AsyncState",
           "COHORT_SALT", "DROPPED", "EDGE_SALT", "FlecsCohortState", "FlecsConfig", "FlecsHParams", "FlecsState",
           "HierarchyConfig", "TrafficHParams", "TrafficModel",
           "TrafficState", "admit_arrivals", "async_hparam_grid",
           "availability_step", "available_mask", "bits_per_round",
           "charge_edges", "cohort_indices", "damped_alpha", "edge_combine",
           "edge_combine_cohort", "edge_of", "edge_round_bits",
           "freeze_on_bit_budget", "hparam_grid", "hparams_bit_budget",
           "hparams_round_bits", "init_async_state", "init_cohort_state",
           "init_edge_bits", "init_state", "init_traffic_state",
           "iters_for_bit_budget", "make_async_step",
           "make_flecs_async_hooks", "make_flecs_cohort_sweep_step",
           "make_flecs_sharded_sweep_step", "make_flecs_sweep_step",
           "participation_mask",
           "replay_delays", "resolve_participation", "run_async_sweep",
           "run_experiment", "run_sharded_sweep", "run_sweep",
           "sharded_state_specs", "sketch", "stationary_distribution",
           "sweep_keys", "sweep_program", "thinned_delays",
           "traffic_hparams", "traffic_send", "validate_hierarchy",
           "worker_mesh"]
