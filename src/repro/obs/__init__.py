"""``repro.obs`` — the simulator's own PMU.

The paper's premise is that good scheduling starts with good counters; the
simulator had the inverse problem: PRs 3-5 moved the whole loop in-graph
(one ``lax.scan`` dispatch per run), so GN residuals, 2-opt rounds,
fallback activations and queue dynamics were computed on device and thrown
away.  This package closes the loop with three layers:

* :mod:`repro.obs.telemetry` — fixed-shape device telemetry rings: a
  per-quantum counter vector stacked as scan ``ys`` and fetched once, so
  the one-dispatch transfer-guard contract is preserved and telemetry-off
  runs stay bit-identical to the uninstrumented engines.
* :mod:`repro.obs.trace` — host span tracing: nestable context-manager
  spans, always on the profiler's clock (``jax.profiler.TraceAnnotation``)
  and in a bounded in-memory record, with Chrome/Perfetto trace-event
  JSON on request.
* :mod:`repro.obs.metrics` — the version-stamped run-report layer: one
  export format (``export_run``/``save_run``/``load_run``) unifying the
  ad-hoc benchmark JSON fields, rendered and diffed by
  ``tools/obs_report.py``.
* :mod:`repro.obs.accuracy` — per-application prediction accuracy over
  the app rings (``app_telemetry=True``): MAPE/bias/RMSE stacks per app
  and per pair, error CCDFs, and a windowed drift detector against a
  recorded budget.

See ``docs/observability.md`` for the counter catalogue and span schema.
"""

from repro.obs.accuracy import (  # noqa: F401
    accuracy_report,
    drift_windows,
    error_ccdf,
    error_stack,
    report_metrics,
)
from repro.obs.metrics import (  # noqa: F401
    OBS_SCHEMA_VERSION,
    READABLE_SCHEMAS,
    export_run,
    load_run,
    save_run,
    version_stamp,
)
from repro.obs.telemetry import (  # noqa: F401
    APP_FIELDS,
    CLOSED_FIELDS,
    FAULT_FIELDS,
    FUSED_DIAG_FIELDS,
    OPEN_FIELDS,
    AppTelemetryLog,
    TelemetryLog,
)
from repro.obs.trace import span  # noqa: F401
