"""automerge_tpu.perf — the performance plane's tooling package.

`python -m automerge_tpu.perf {report,check,contention,doctor,top}`:

- `report`   — print the bench-history trajectory (`bench_history.jsonl`)
               plus the latest run's perf telemetry when available.
- `check`    — the regression gate: current run vs the rolling
               same-backend median; nonzero exit on throughput regression
               or compile-count growth (history.py).
- `doctor`   — ranked root-cause report (doctor.py): live against a
               fleet, or post-mortem against BENCH_DETAIL.json /
               flight-recorder dumps.
- `top`      — live terminal dashboard over the fleet collector
               (fleet.py: scrape over `{"metrics": "pull"}`, straggler
               detection; slo.py: the SLO verdict strip).

A kernel's share of its roofline and the stage breakdown of a served
request are measured on the chip by the benchmark (`benchmarks/`: the
`trace_roofline` reader, and perfscope's phases on the profiler's clock).

The runtime half of the performance plane (compile telemetry, phase
attribution, memory gauges) lives in `automerge_tpu/utils/perfscope.py`;
this package is the offline/CLI half. `history` is deliberately
pure-stdlib so `bench.py`'s jax-free parent process can load it by file
path. See docs/OBSERVABILITY.md "Performance plane".
"""

from . import history  # noqa: F401  (stdlib-only; safe to import eagerly)
