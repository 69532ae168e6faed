"""End-to-end episode observability: span tracer + Perfetto export +
critical-path latency attribution (ISSUE 9).

``Tracer`` records per-episode lifecycle marks and per-track spans from
every disaggregated stage; ``phase.phase`` puts each stage's named
phases on the profiler's trace (and, given a tracer, into its ring);
``export_chrome`` renders Perfetto-loadable JSON; ``repro.obs.report``
decomposes each episode's submission→commit latency into additive
per-stage components and names each tenant's bottleneck stage. See ``README.md`` in this package."""
from .tracer import COMPONENT_OF, TERMINAL_STATES, Tracer

__all__ = ["Tracer", "COMPONENT_OF", "TERMINAL_STATES"]
