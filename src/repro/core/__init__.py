"""The paper's contribution: noise injection for bottleneck analysis, in JAX.

Two injection sites (DESIGN.md §2):
  - loop-level  (core.loopnoise + core.controller.loop_region): patterns
    emitted inside the target loop body — the LLVM-pass analogue; measured
    absorption on the host is genuine OoO absorption.
  - graph-level (core.noise + core.injector): patterns injected around a whole
    jitted train/serve step — used with payload verification and the analytic
    saturation model for the TPU dry-run target.
"""
from repro.core.absorption import (  # noqa: F401
    AbsorptionCurve,
    AbsorptionFit,
    MeasureTimeout,
    Sample,
    absorption,
    cluster_times,
    fit_three_phase,
    measure,
    measure_sample,
    sweep,
)
from repro.core.analytic import (  # noqa: F401
    StepTerms,
    compare_memory_systems,
    predict_absorption,
    predict_curve,
)
from repro.core.campaign import (AnalyticCampaign, Campaign, CampaignStats,  # noqa: F401
                                 CampaignStore, CampaignStoreError,
                                 CompactStats, MergeStats, PairStatus,
                                 compact_store, host_store, merge_stores,
                                 read_store_records, worker_store)
from repro.core.segments import (SegmentStore, io_tally, is_segmented,  # noqa: F401
                                 manifest_status, remove_store, segments_dir,
                                 store_exists)
from repro.core.classifier import (BottleneckReport, apply_audit_evidence,  # noqa: F401
                                   apply_quality_evidence, classify,
                                   cross_check_with_decan)
from repro.core.calibration import (CALIB_MODES, EXPECTED, REGIMES,  # noqa: F401
                                    CalibrationResult, calibrate_targets,
                                    fit_thresholds, forced_regime, hw_name,
                                    resolve_thresholds, run_calibration)
from repro.core.strategy import (StrategyError, StrategyTree, default_tree,  # noqa: F401
                                 load_tree, strategies_dir)
from repro.core.quality import (QualityPolicy, RemeasureBudget,  # noqa: F401
                                measure_quality, quality_from_dict)
from repro.core.controller import Controller, RegionReport, RegionTarget, loop_region  # noqa: F401
from repro.core.decan import DecanResult, DecanTarget, run_decan  # noqa: F401
from repro.core.injector import (inject, inject_rt, init_state,  # noqa: F401
                                 step_region, verify_semantics)
from repro.core.loopnoise import LoopNoise, make_loop_modes, noisy_loop  # noqa: F401
from repro.core.noise import NOISE_SCOPE, NoiseMode, NoiseScale, PatternCost, make_modes  # noqa: F401
from repro.core.payload import InjectionReport, analyze_injection, body_size  # noqa: F401
