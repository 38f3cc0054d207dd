"""The ``Observability`` bundle the FL stack threads through itself.

One object carries the tracer, the metrics registry and the optional
``jax.profiler`` hook; ``run_fedssl(obs=...)``, the engines, the transport
and the fleet simulator all hold a reference (``NOOP_OBS`` by default —
everything off, near-zero overhead) and record unconditionally.

``make_obs(trace=..., metrics=..., profile_dir=...)`` builds an enabled
bundle; ``obs.export(...)`` writes whichever artifacts were requested
(JSONL trace, Chrome trace, metrics CSV). A run given a profile directory
fails if ``jax.profiler`` cannot start or stop; it never carries on
untraced.
"""
from __future__ import annotations

from typing import Optional

from repro.obs import export as export_mod
from repro.obs.metrics import NOOP_METRICS, MetricsRegistry
from repro.obs.trace import NOOP_TRACER, Tracer, is_tracing


class Observability:
    """Tracer + metrics + profiler hooks. Prefer ``make_obs``."""

    def __init__(self, tracer=NOOP_TRACER, metrics=NOOP_METRICS,
                 profile_dir: Optional[str] = None, health=None,
                 measure_resources: bool = False):
        self.tracer = tracer
        self.metrics = metrics
        self.profile_dir = profile_dir
        self.health = health
        # opt-in: the driver AOT-lowers each new stage's round program
        # and attaches measured cost_analysis attrs (res.*) to the
        # stage-opening round span — a few seconds per stage
        self.measure_resources = measure_resources
        self._profiling = False

    @property
    def enabled(self) -> bool:
        return (is_tracing(self.tracer)
                or isinstance(self.metrics, MetricsRegistry)
                or self.profile_dir is not None
                or self.health is not None)

    # -- jax.profiler hooks: a run asked to profile fails if it cannot ------
    def start_profiler(self):
        if self.profile_dir is None or self._profiling:
            return
        import jax
        jax.profiler.start_trace(self.profile_dir)
        self._profiling = True

    def stop_profiler(self):
        if not self._profiling:
            return
        import jax
        self._profiling = False
        jax.profiler.stop_trace()

    # -- artifact export -----------------------------------------------------
    def export(self, *, trace_jsonl=None, chrome_trace=None,
               metrics_csv=None, health_json=None, **meta):
        """Write the requested artifacts; returns {kind: path}."""
        written = {}
        if trace_jsonl and is_tracing(self.tracer):
            written["trace_jsonl"] = export_mod.write_jsonl(
                self.tracer, trace_jsonl, **meta)
        if chrome_trace and is_tracing(self.tracer):
            written["chrome_trace"] = export_mod.write_chrome_trace(
                self.tracer, chrome_trace, **meta)
        if metrics_csv and isinstance(self.metrics, MetricsRegistry):
            written["metrics_csv"] = export_mod.write_metrics_csv(
                self.metrics, metrics_csv)
        if health_json and self.health is not None:
            from repro.obs.health import write_health_json
            write_health_json(health_json, self.health, **meta)
            written["health_json"] = health_json
        return written


NOOP_OBS = Observability()


def make_obs(*, trace: bool = False, metrics: bool = False,
             profile_dir: Optional[str] = None, clock=None,
             health: bool = False, halt_on_unhealthy: bool = False,
             measure_resources: bool = False,
             **meta) -> Observability:
    """Build an enabled bundle; extra kwargs become trace run metadata.
    ``health=True`` attaches a ``HealthMonitor`` the driver feeds each
    round; ``halt_on_unhealthy`` arms its halt-on-fatal hook."""
    if trace:
        tracer = Tracer(clock) if clock is not None else Tracer()
        tracer.meta.update(meta)
    else:
        tracer = NOOP_TRACER
    monitor = None
    if health or halt_on_unhealthy:
        from repro.obs.health import HealthMonitor
        monitor = HealthMonitor(halt_on_fatal=halt_on_unhealthy)
    return Observability(
        tracer=tracer,
        metrics=MetricsRegistry() if metrics else NOOP_METRICS,
        profile_dir=profile_dir, health=monitor,
        measure_resources=measure_resources)
