"""99th percentile of every gap between consecutive tokens of a request,
both inside the window, on the host clock. A step slowed by a pause of
the host process delays every busy slot's gap at once, so the few slow
steps of a window set it; the end-to-end ``itl_p95_ms`` looks past them."""
from chipbench.metrics._common import pct


def read(run):
    return pct((run.get("counters") or {}).get("itl_ms") or [], 99)
