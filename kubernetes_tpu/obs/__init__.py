"""Shared observability machinery (ISSUE 9).

The flight recorder (PR 3/PR 7) proved a set of idioms on the scheduler —
O(1) per-batch taps on perf_counter, bounded record rings, windowed
log-bucket stage histograms with exact-while-complete percentiles, measured
self-time against a <2% budget. This package factors the reusable half out
of scheduler/flightrec.py so the rest of the control plane (the ~20
reconcile controllers, the store's watch bus) can inherit the same
machinery instead of reinventing weaker copies:

  obs.recorder   — StageClock + RingRecorder (the generic bounded ring with
                   per-stage totals/histograms and the p50/p99 stage table);
                   StageClock's stages are jax.profiler TraceMe spans.
  obs.gcpause    — the process-wide garbage-collection pause counter (one
                   gc.callbacks hook) the flight recorder and the resource
                   sampler read.
  obs.reconcile  — ReconcileRecorder: per-loop reconcile spans for
                   controllers/base.py, plus the live-controller registry
                   behind GET /debug/controlstats and `ktl controller stats`.
  obs.timeseries — TimeSeriesRecorder: fixed-interval windows over the batch
                   pipeline (per-stage p50/p99, pods/s, probe columns) plus
                   the fit_slope/drift_ratio trend math the leak gates in
                   scheduler/slo.py consume (ISSUE 13).
  obs.resource   — ResourceSampler: RSS / GC / live-object / per-thread CPU
                   sampling with a measured-clock honesty flag — the
                   steady-state leak and GIL-overlap signal (ISSUE 13).
"""

from .recorder import (  # noqa: F401
    STAGE_P_BUCKETS,
    RingRecorder,
    StageClock,
    nearest_rank,
)
from .timeseries import (  # noqa: F401
    TimeSeriesRecorder,
    drift_ratio,
    fit_slope,
)
from .resource import ResourceSampler  # noqa: F401
