"""Per-layer readers: found by name, silent where there is nothing to
read, and the roofline reading refuses a step with more than one kind of
Mosaic kernel."""
import pytest

from chipbench import harness

PEAKS = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}


def test_every_per_layer_metric_has_a_reader():
    bench = harness.load_bench()
    for m in bench["per_layer"]:
        assert harness.load_reader(m["name"]).read({}) is None


def test_readings_from_counters_and_trace():
    run = {"chips": 1, "peaks": PEAKS,
           "counters": {"traced_model_flops": 197e12 * 0.1,
                        "lanes_valid": 30, "lanes_total": 120,
                        "traced_steps": 4, "traced_attn_flops": 197e9,
                        "traced_attn_bytes": 819e9 * 0.002,
                        "compiles_in_window": 0},
           "trace": {"window_s": 2.0, "busy_s": 1.5, "collective_s": 0.2,
                     "kernel_s": {"gqa_paged_decode_attn_2d": 0.004}}}
    r = lambda n: harness.load_reader(n).read(run)
    assert r("mfu.decode") == pytest.approx(5.0)
    assert r("lane_use.decode") == pytest.approx(25.0)
    assert r("idle_share.train") == pytest.approx(25.0)
    assert r("collective_share.train") == pytest.approx(10.0)
    # max(1e-3 s of FLOPs, 2e-3 s of bytes) over 4e-3 s of kernel
    assert r("attn_roofline.decode") == pytest.approx(50.0)
    assert r("compiles_in_window.train") == 0
    run["trace"]["kernel_s"]["other_kernel"] = 0.001
    with pytest.raises(RuntimeError):
        r("attn_roofline.decode")


def test_result_line_keeps_checks_last():
    bench = harness.load_bench()
    cell = next(w for w in bench["workloads"] if w["name"] == "yi6b-decode")
    out = {"e2e": {"setup_s": 50.0, "serve_tokens_per_s": 70.0,
                   "itl_p95_ms": 300.0},
           "checks": [{"name": "gap", "value": 0.1, "limit": 0.5,
                       "rule": "value <= limit", "ok": True}],
           "attempted": 10, "failed": 0, "memory_peak_bytes": 1}
    dev = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}
    line = harness.build_line(bench, cell, out, False, dev)
    assert list(line)[-1] == "checks" and line["correct"] is True
    assert set(line["metrics"]) == {"setup_s", "serve_tokens_per_s",
                                    "itl_p95_ms"}
    out["checks"][0]["ok"] = False
    assert harness.build_line(bench, cell, out, False, dev)["correct"] \
        is False
