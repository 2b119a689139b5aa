"""Host time per answered question: the benchmark's question span minus
the union of device-busy intervals inside it (profiler trace). Covers
the entry and experiment build (core/scenarios.py, core/bench.py) and
result marshalling."""
from chipbench.trace import complete


def read(run):
    host = run.trace["question_host_s"] if complete(run.trace) else []
    return 1e3 * sum(host) / len(host) if host else None
