"""Share of chip 0's traced window spent in collective operations
(all-reduce, all-gather, collective-permute, ...) on its TensorCore's op
line, where nothing else runs: the exposed part of the TP psums and of
the migration broadcast."""


def read(run):
    t = run.get("trace") or {}
    if not t.get("window_s"):
        return None
    return 100.0 * t["collective_s"] / t["window_s"]
