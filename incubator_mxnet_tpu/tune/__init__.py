"""mx.tune — the deployment-profile autotuner.

The repo's perf knobs (serve decode_steps / prefill_lanes / max_slots /
draft_tokens / kv_dtype, train remat x donate and conv layout, io
workers / lookahead / shm budget, batcher buckets, dispatch bulk size)
all have measured, workload-dependent winners — found by hand, PR by PR,
and living only in `tune.search.HAND_TUNED`. This subsystem makes that
a closed loop, the JAX-native equivalent of the reference's
oneDNN/autotune layer:

  * `tune.space`   — the typed, registered knob catalog (the swept
    space, mxlint-checked against docs/TUNING.md);
  * `tune.search`  — deterministic coordinate-descent sweeps through
    crash-isolated measurement subprocesses;
  * `tune.profile` — `DeploymentProfile`: winners keyed by (model
    fingerprint, hardware fingerprint), persisted beside the persistent
    compile cache, activated at startup so a fresh replica is both
    warm-compiled AND well-tuned.

Operator entry point: `tools/mxtune.py`.
"""
from .space import (KNOBS, NON_TUNABLE_ENV, Knob, catalog, knob,
                    knobs_for_phase, phases, knob_env_vars,
                    default_assignment, validate_assignment,
                    scrubbed_env)
from .profile import (DeploymentProfile, model_fingerprint,
                      hardware_fingerprint, profile_dir, profile_path,
                      activate, deactivate, active, resolve, lookup,
                      disabled, TUNE_STATS, tune_stats)
from .search import HAND_TUNED, sweep, build_profile, plan

__all__ = [
    "KNOBS", "NON_TUNABLE_ENV", "Knob", "catalog", "knob",
    "knobs_for_phase", "phases", "knob_env_vars", "default_assignment",
    "validate_assignment", "scrubbed_env",
    "DeploymentProfile", "model_fingerprint", "hardware_fingerprint",
    "profile_dir", "profile_path", "activate", "deactivate", "active",
    "resolve", "lookup", "disabled", "TUNE_STATS", "tune_stats",
    "HAND_TUNED", "sweep", "build_profile", "plan",
]
