"""Deterministic observability: simulated-clock tracing + metrics +
alerting.

    trace.py     span/event tracer keyed to the simulated clocks; exports
                 Chrome/Perfetto trace-event JSON, bit-identical per seed
    metrics.py   counters / gauges / fixed-bucket histograms with exact
                 quantiles — the one percentile implementation in the repo
    watch.py     Watchtower: declarative alert rules (threshold /
                 burn-rate / EWMA-drift) evaluated over the registry on
                 the simulated clock; bit-identical alert JSONL per seed
    recorder.py  FlightRecorder: bounded ring of recent trace events,
                 dumps postmortem bundles on alert or injected fault
    fsio.py      atomic artifact writes (tmp + fsync + os.replace)

Instrumented subsystems (all hooks are no-ops when no tracer/registry is
attached — the hot paths are untouched on the default path):

    runtime/scheduler.py   per-peer step/publish/recover spans, mailbox
                           staleness + comm counters
    train/loop.py          per-step spans, exchange markers, comm counters
    serve/fleet/           per-request span trees (admit→queue→prefill→
                           decode→…→emit, surviving migration), per-tick
                           engine spans, KV-pool occupancy and analytic
                           decode HBM/FLOP counter streams

Surfaced as ``--trace out.json --metrics out-metrics.json`` on
``repro.launch.train``, ``repro.launch.serve`` and ``repro.launch.sweep``;
``tools/trace_check.py`` validates exported traces in CI. See
docs/observability.md.

Two clocks. This package's tracer runs on simulated clocks: it makes
deterministic CI artifacts and never measures speed. Time on the chip is
read from the JAX profiler's own clock: the named scopes on the training
step's layers (``embed``, ``layers``/``attn``/``mlp``, ``head``, ``loss``,
``exchange``, ``optimizer``, ``metrics``) and the ``train.dispatch`` /
``train.host_exchange`` spans of ``StepBundle.apply``, which
``chipbench`` reduces from a traced run on the chip.
"""
from repro.obs.fsio import atomic_write_text  # noqa: F401
from repro.obs.metrics import (DEFAULT_BUCKETS, GAUGE_WINDOW,  # noqa: F401
                               METRICS_SCHEMA_VERSION, Counter, Gauge,
                               Histogram, MetricsRegistry)
from repro.obs.recorder import (POSTMORTEM_SCHEMA_VERSION,  # noqa: F401
                                FlightRecorder)
from repro.obs.trace import (TRACE_SCHEMA_VERSION, TraceError,  # noqa: F401
                             Tracer, for_sim_ms, for_sim_seconds, for_steps)
from repro.obs.watch import (ALERTS_SCHEMA_VERSION, Rule,  # noqa: F401
                             Watchtower, default_rules, load_rules,
                             parse_rules)
